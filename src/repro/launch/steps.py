"""Step builders + input specs for every (arch x shape) dry-run cell.

``input_specs(cfg, shape)`` returns weak-type-correct ShapeDtypeStruct
stand-ins for every input of the step function (no device allocation);
``build_cell(cfg, shape, mesh)`` returns ``(step_fn, in_shardings,
out_shardings, abstract_args)`` ready for ``jax.jit(...).lower(...)``.

Cells:
* train  — full train step: loss + grads + AdamW update (donated state)
* prefill — forward logits over the full sequence
* decode — one-token serve step against a pre-filled KV cache / SSM state
* detector — fixed-batch frame classifier over an embeds-in backbone:
  the gated cascade's downstream step
  (:class:`repro.launch.cascade.CascadeService` batches HP frames
  drained from the gate runners through it — the gate→detect loop the
  paper serves end to end)
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed import sharding as shlib
from repro.models import attention, lm, ssm, xlstm
from repro.models.lm import Batch, DecodeBatch
from repro.train import optim

Array = jax.Array


class Cell(NamedTuple):
    step_fn: Any
    in_shardings: Any
    out_shardings: Any
    abstract_args: tuple
    donate_argnums: tuple


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _batch_specs(cfg: ModelConfig, shape: ShapeConfig) -> Batch:
    b, s = shape.global_batch, shape.seq_len
    tokens = None if cfg.embeds_in else _sds((b, s), jnp.int32)
    labels = _sds((b, s), jnp.int32)
    embeds = None
    if cfg.embeds_in:
        embeds = _sds((b, s, cfg.d_model), jnp.bfloat16)
    elif cfg.family == "vlm":
        embeds = _sds((b, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
    return Batch(tokens=tokens, labels=labels, embeds=embeds)


def _batch_shardings(cfg: ModelConfig, shape: ShapeConfig, mesh,
                     rules=None) -> Batch:
    def sh(sds, axes):
        if sds is None:
            return None
        return shlib.logical_sharding(sds.shape, axes, mesh, rules)

    specs = _batch_specs(cfg, shape)
    return Batch(
        tokens=sh(specs.tokens, ("act_batch", "act_seq")),
        labels=sh(specs.labels, ("act_batch", "act_seq")),
        embeds=sh(specs.embeds, ("act_batch", "act_seq", "act_embed")),
    )


def _decode_state_axes(model: lm.Model):
    """Logical-axis tree matching ``decode_state_spec`` (leading layer dim)."""
    cfg = model.cfg
    if cfg.family in ("dense", "moe", "vlm"):
        ax = attention.cache_axes()
        return attention.KVCache(("layers", *ax.k), ("layers", *ax.v))
    if cfg.family == "hybrid":
        sax = ssm.state_axes()
        aax = attention.cache_axes()
        return {
            "mamba": ssm.SSMState(("layers", *sax.ssm),
                                  ("layers", *sax.conv)),
            "attn": attention.KVCache(("layers", *aax.k),
                                      ("layers", *aax.v)),
        }
    if cfg.family == "ssm":
        from repro.models.lm import _xlstm_kinds
        out = []
        for kind in _xlstm_kinds(cfg):
            out.append(xlstm.slstm_state_axes() if kind == "slstm"
                       else xlstm.mlstm_state_axes())
        return out
    raise ValueError(cfg.family)


def _tree_shardings(spec_tree, axes_tree, mesh, rules=None):
    return jax.tree.map(
        lambda sds, axes: shlib.logical_sharding(sds.shape, tuple(axes),
                                                 mesh, rules),
        spec_tree, axes_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _replicated(mesh):
    return NamedSharding(mesh, PS())


# ---------------------------------------------------------------------------
# Cell builders
# ---------------------------------------------------------------------------

def make_optimizer(cfg: ModelConfig) -> optim.AdamW:
    return optim.AdamW(lr=optim.warmup_cosine(3e-4, 2000, 100_000),
                       weight_decay=0.1)


def build_train_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
                     rules=None) -> Cell:
    model = lm.build(cfg)
    opt = make_optimizer(cfg)
    compute_dtype = model.compute_dtype

    def train_step(params, opt_state, batch):
        # mixed precision: cast fp32 master weights to bf16 ONCE, on their
        # FSDP shards, so the per-layer weight all-gather moves bf16 (2x
        # less ICI traffic) and the convert isn't re-done per use
        # (§Perf hillclimb C1).
        def cast(p):
            return p.astype(compute_dtype) if p.dtype == jnp.float32 else p

        cast_params = jax.tree.map(cast, params)
        loss, grads = jax.value_and_grad(
            lambda cp: model.loss(cp, batch))(cast_params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        return params, opt_state, loss

    p_abs = model.abstract_params()
    p_sh = model.param_shardings(mesh, rules)
    opt_abs = optim.AdamWState(
        step=_sds((), jnp.int32),
        mu=jax.tree.map(lambda s: _sds(s.shape, s.dtype), p_abs),
        nu=jax.tree.map(lambda s: _sds(s.shape, s.dtype), p_abs))
    opt_sh = optim.AdamWState(step=_replicated(mesh), mu=p_sh, nu=p_sh)
    b_abs = _batch_specs(cfg, shape)
    b_sh = _batch_shardings(cfg, shape, mesh, rules)

    return Cell(
        step_fn=train_step,
        in_shardings=(p_sh, opt_sh, b_sh),
        out_shardings=(p_sh, opt_sh, _replicated(mesh)),
        abstract_args=(p_abs, opt_abs, b_abs),
        donate_argnums=(0, 1),
    )


def build_prefill_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
                       rules=None) -> Cell:
    model = lm.build(cfg)

    def prefill_step(params, batch):
        logits, _ = model.forward(params, batch)
        return logits

    p_abs = model.abstract_params()
    p_sh = model.param_shardings(mesh, rules)
    b_abs = _batch_specs(cfg, shape)
    b_sh = _batch_shardings(cfg, shape, mesh, rules)
    s_img = 0 if (cfg.family != "vlm" or cfg.embeds_in) \
        else 0  # vlm logits are text-only (image prefix stripped)
    out_shape = (shape.global_batch, shape.seq_len + s_img, cfg.vocab)
    out_sh = shlib.logical_sharding(out_shape,
                                    ("act_batch", "act_seq", "act_vocab"),
                                    mesh, rules)
    return Cell(
        step_fn=prefill_step,
        in_shardings=(p_sh, b_sh),
        out_shardings=out_sh,
        abstract_args=(p_abs, b_abs),
        donate_argnums=(),
    )


def build_decode_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
                      rules=None) -> Cell:
    model = lm.build(cfg)

    def serve_step(params, state, batch):
        logits, state = model.decode_step(params, state, batch)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1)
        return next_tok, state

    b = shape.global_batch
    p_abs = model.abstract_params()
    p_sh = model.param_shardings(mesh, rules)
    st_abs = model.decode_state_spec(batch=b, max_seq=shape.seq_len)
    st_ax = _decode_state_axes(model)
    st_sh = _tree_shardings(st_abs, st_ax, mesh, rules)
    db_abs = DecodeBatch(tokens=_sds((b, 1), jnp.int32),
                         index=_sds((), jnp.int32))
    db_sh = DecodeBatch(
        tokens=shlib.logical_sharding((b, 1), ("act_batch", None), mesh,
                                      rules),
        index=_replicated(mesh))
    tok_sh = shlib.logical_sharding((b,), ("act_batch",), mesh, rules)
    return Cell(
        step_fn=serve_step,
        in_shardings=(p_sh, st_sh, db_sh),
        out_shardings=(tok_sh, st_sh),
        abstract_args=(p_abs, st_abs, db_abs),
        donate_argnums=(1,),
    )


def detector_seq_len(frame_hw: tuple[int, int], patch: int) -> int:
    """Patch-token sequence length a detector frame unrolls to."""
    H, W = frame_hw
    if patch < 1 or H % patch or W % patch:
        raise ValueError(f"patch {patch} must divide frame {frame_hw}")
    return (H // patch) * (W // patch)


def build_detector_cell(cfg: ModelConfig, *, batch: int,
                        frame_hw: tuple[int, int], patch: int,
                        n_out: int = 2, mesh=None, rules=None) -> Cell:
    """Downstream-backbone detector step for the gated cascade.

    ``detector_step(params, frames)``: a fixed ``(batch, H, W)`` float32
    block of HP frames → ``((batch, n_out)`` float32 class logits,
    ``(expert layers, n_experts)`` int32 pairs per held expert``)``. Each
    frame is patchified to ``seq = (H/patch)*(W/patch)`` tokens, linearly
    embedded (``params["embedder"]``: ``proj (patch², d_model)`` +
    ``pos (seq, d_model)``), and run through an **embeds-in** LM backbone
    (``params["backbone"]``); only the last position is unembedded, and
    its first ``n_out`` vocab logits are the detection head (at smoke
    scale the backbone is the hubert-style encoder — the cascade's
    stand-in for the paper's YOLO detector).

    The batch axis takes one of two paths, by whether the config has
    experts:

    * Without experts, ``jax.lax.map``, NOT ``vmap``: every row executes
      the identical unbatched program, so a frame's logits are bitwise
      independent of its batch position and of whatever else shares the
      batch — including zero-padded slack rows (batched service output ≡
      eager per-frame evaluation). The pair counts are ``(0, 0)``.
    * With experts, the whole block is one batch through the trunk, so
      each expert layer runs every frame's routed pairs at once and reads
      its weights once a batch; attention stays within each frame. A
      frame's logits no longer depend on the others' routing (the
      dropless layer drops nothing), but the batched products may round
      differently with the rows beside them.

    With a ``mesh`` the backbone params shard via ``param_shardings``
    (frames and the tiny embedder replicate); ``mesh=None`` builds an
    unsharded cell with ``None`` shardings.
    """
    if not cfg.embeds_in:
        raise ValueError(f"{cfg.arch_id}: detector backbone needs an "
                         "embeds-in config (the patch embedder replaces "
                         "the token embedding)")
    if n_out < 1 or n_out > cfg.vocab:
        raise ValueError(f"n_out {n_out} must be in [1, vocab={cfg.vocab}]")
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    model = lm.build(cfg)
    H, W = frame_hw
    seq = detector_seq_len(frame_hw, patch)

    def logits_of(params, frames):
        """(b, H, W) -> ((b, n_out) logits, pair counts)."""
        b = frames.shape[0]
        p = frames.reshape(b, H // patch, patch, W // patch, patch)
        p = p.transpose(0, 1, 3, 2, 4).reshape(b, seq, patch * patch)
        emb = (p.astype(jnp.float32) @ params["embedder"]["proj"]
               + params["embedder"]["pos"])
        bt = Batch(tokens=None, labels=jnp.zeros((b, seq), jnp.int32),
                   embeds=emb.astype(model.compute_dtype))
        logits, loads = model.last_logits(params["backbone"], bt)
        return logits[:, 0, :n_out].astype(jnp.float32), loads

    def detector_step(params, frames):
        if cfg.n_experts:
            return logits_of(params, frames)
        logits = jax.lax.map(lambda f: logits_of(params, f[None])[0][0],
                             frames)
        return logits, jnp.zeros((0, 0), jnp.int32)

    p_abs = {
        "backbone": model.abstract_params(),
        "embedder": {
            "proj": _sds((patch * patch, cfg.d_model), jnp.float32),
            "pos": _sds((seq, cfg.d_model), jnp.float32),
        },
    }
    f_abs = _sds((batch, H, W), jnp.float32)
    if mesh is None:
        return Cell(step_fn=detector_step, in_shardings=None,
                    out_shardings=None, abstract_args=(p_abs, f_abs),
                    donate_argnums=())
    p_sh = {
        "backbone": model.param_shardings(mesh, rules),
        "embedder": {"proj": _replicated(mesh), "pos": _replicated(mesh)},
    }
    return Cell(step_fn=detector_step,
                in_shardings=(p_sh, _replicated(mesh)),
                out_shardings=(_replicated(mesh), _replicated(mesh)),
                abstract_args=(p_abs, f_abs),
                donate_argnums=())


def init_detector_params(key, cfg: ModelConfig, *,
                         frame_hw: tuple[int, int], patch: int) -> dict:
    """Concrete detector params matching :func:`build_detector_cell`."""
    model = lm.build(cfg)
    seq = detector_seq_len(frame_hw, patch)
    k_b, k_p, k_q = jax.random.split(jax.random.PRNGKey(0)
                                     if isinstance(key, int) else key, 3)
    scale = 1.0 / float(patch)
    return {
        "backbone": model.init(k_b),
        "embedder": {
            "proj": scale * jax.random.normal(
                k_p, (patch * patch, cfg.d_model), jnp.float32),
            "pos": 0.02 * jax.random.normal(
                k_q, (seq, cfg.d_model), jnp.float32),
        },
    }


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               rules=None) -> Cell:
    builder = {"train": build_train_cell,
               "prefill": build_prefill_cell,
               "decode": build_decode_cell}[shape.kind]
    return builder(cfg, shape, mesh, rules)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> tuple:
    """ShapeDtypeStruct stand-ins for every step input (no allocation)."""
    mesh = None
    # specs don't need a mesh; reuse the cell builder with a null mesh via
    # a tiny shim that skips shardings
    if shape.kind == "train":
        model = lm.build(cfg)
        p_abs = model.abstract_params()
        opt_abs = optim.AdamWState(
            step=_sds((), jnp.int32),
            mu=jax.tree.map(lambda s: _sds(s.shape, s.dtype), p_abs),
            nu=jax.tree.map(lambda s: _sds(s.shape, s.dtype), p_abs))
        return (p_abs, opt_abs, _batch_specs(cfg, shape))
    if shape.kind == "prefill":
        model = lm.build(cfg)
        return (model.abstract_params(), _batch_specs(cfg, shape))
    model = lm.build(cfg)
    st_abs = model.decode_state_spec(batch=shape.global_batch,
                                     max_seq=shape.seq_len)
    db = DecodeBatch(tokens=_sds((shape.global_batch, 1), jnp.int32),
                     index=_sds((), jnp.int32))
    return (model.abstract_params(), st_abs, db)
