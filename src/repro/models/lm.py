"""LM model facade: every assigned architecture behind one API.

``build(cfg)`` -> :class:`Model` with

* ``spec()`` / ``init(key)`` / ``param_shardings(mesh)``
* ``forward(params, batch)``            — logits for train/prefill
* ``loss(params, batch)``               — next-token (or masked-encoder) loss
* ``decode_state_spec(batch, max_seq)`` — KV caches / SSM states
* ``decode_step(params, state, batch)`` — one-token serve step

Layer stacking: homogeneous families (dense/moe/encoder/vlm) use
``jax.lax.scan`` over stacked layer params (compact HLO for 95-layer
stacks) with per-layer remat; a config's leading dense layers
(``first_dense_layers``, before a scanned expert stack) run unscanned
first. Heterogeneous families (zamba2 hybrid,
xlstm) use python loops over per-layer param lists — their layer counts
are small.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import attention, common, mlp, ssm, xlstm
from repro.models.common import P

Array = jax.Array


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def _attn_cfg(cfg: ModelConfig) -> attention.AttnConfig:
    return attention.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.resolved_head_dim, causal=cfg.causal and not cfg.is_encoder,
        rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm, norm=cfg.norm)


def _mla_cfg(cfg: ModelConfig) -> attention.MLAConfig:
    return attention.MLAConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        causal=cfg.causal and not cfg.is_encoder, rope_theta=cfg.rope_theta)


def _mlp_cfg(cfg: ModelConfig, d_ff: int | None = None) -> mlp.MLPConfig:
    return mlp.MLPConfig(d_model=cfg.d_model, d_ff=d_ff or cfg.d_ff,
                         activation=cfg.activation,
                         gated=cfg.activation == "silu")


def _held_cfg(cfg: ModelConfig) -> mlp.HeldExpertsConfig:
    return mlp.HeldExpertsConfig(
        d_model=cfg.d_model, d_ff=cfg.d_ff, n_held=cfg.n_experts,
        n_routed=cfg.router_experts, top_k=cfg.top_k,
        offset=cfg.expert_offset, n_shared=cfg.n_shared_experts,
        scaling=cfg.routed_scaling_factor)


def _moe_cfg(cfg: ModelConfig) -> mlp.MoEConfig:
    return mlp.MoEConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                         n_experts=cfg.n_experts, top_k=cfg.top_k,
                         capacity_factor=cfg.capacity_factor,
                         activation=cfg.activation,
                         dispatch_int8=cfg.moe_dispatch_int8)


def _ssm_cfg(cfg: ModelConfig) -> ssm.SSMConfig:
    return ssm.SSMConfig(
        d_model=cfg.d_model, d_inner=cfg.d_inner, n_heads=cfg.ssm_heads,
        head_dim=cfg.ssm_head_dim, d_state=cfg.ssm_state,
        chunk=cfg.ssm_chunk)


def _xlstm_cfg(cfg: ModelConfig) -> xlstm.XLSTMConfig:
    return xlstm.XLSTMConfig(d_model=cfg.d_model, n_heads=cfg.n_heads,
                             chunk=cfg.ssm_chunk)


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Transformer layer (dense / moe / encoder / vlm — all share this block)
# ---------------------------------------------------------------------------

def _tf_layer_spec(cfg: ModelConfig, dense: bool = False) -> dict:
    """One block; ``dense`` makes a leading dense layer of an expert model
    (FFN ``dense_d_ff`` wide)."""
    s = {
        "attn_norm": common.norm_spec(cfg.d_model, cfg.norm),
        "attn": (attention.mla_spec(_mla_cfg(cfg)) if cfg.kv_lora_rank
                 else attention.spec(_attn_cfg(cfg))),
        "mlp_norm": common.norm_spec(cfg.d_model, cfg.norm),
    }
    if dense:
        s["mlp"] = mlp.spec(_mlp_cfg(cfg, cfg.dense_d_ff))
    elif cfg.n_experts and cfg.router_experts:
        s["experts"] = mlp.held_spec(_held_cfg(cfg))
    elif cfg.n_experts:
        s["moe"] = mlp.moe_spec(_moe_cfg(cfg))
    else:
        s["mlp"] = mlp.spec(_mlp_cfg(cfg))
    return s


def _seq_gather(x: Array) -> Array:
    """Explicit bf16 gather point for the sequence-parallel residual.

    The optimization barrier pins the collective to the low-precision
    tensor: without it XLA hoists the norm's f32 upcast above the
    all-gather, doubling SP collective bytes (§Perf hillclimb C3).
    """
    xg = shard(x, "act_batch", "act_seq", "act_embed")
    return jax.lax.optimization_barrier(xg)


def _to_resid(y: Array) -> Array:
    return shard(y, "act_batch", "act_resid_seq", "act_embed")


def _tf_layer(params: dict, x: Array, cfg: ModelConfig
              ) -> tuple[Array, Array, Array]:
    """Pre-norm transformer block (sequence-parallel residual stream).

    Returns (x, moe_aux, loads): ``loads`` holds the pairs each held
    expert computed (empty where the block holds no dropless experts).
    The feed-forward kind follows the block's params."""
    a = common.apply_norm(_seq_gather(x), params.get("attn_norm"), cfg.norm,
                          cfg.norm_eps)
    attn = (attention.mla_full(params["attn"], a, _mla_cfg(cfg))
            if cfg.kv_lora_rank
            else attention.full(params["attn"], a, _attn_cfg(cfg)))
    x = x + _to_resid(attn)
    m = common.apply_norm(_seq_gather(x), params.get("mlp_norm"), cfg.norm,
                          cfg.norm_eps)
    aux, loads = 0.0, jnp.zeros((0,), jnp.int32)
    if "experts" in params:
        out, loads = mlp.held_apply(params["experts"], m, _held_cfg(cfg))
    elif "moe" in params:
        out, aux = mlp.moe_apply(params["moe"], m, _moe_cfg(cfg))
    else:
        out = mlp.apply(params["mlp"], m, _mlp_cfg(cfg))
    return x + _to_resid(out), jnp.asarray(aux, jnp.float32), loads


def _tf_layer_decode(params: dict, x: Array, cache: attention.KVCache,
                     index: Array, cfg: ModelConfig
                     ) -> tuple[Array, attention.KVCache]:
    a = common.apply_norm(x, params.get("attn_norm"), cfg.norm)
    attn_out, cache = attention.decode_step(params["attn"], a, cache,
                                            index, _attn_cfg(cfg))
    x = x + attn_out
    m = common.apply_norm(x, params.get("mlp_norm"), cfg.norm)
    if cfg.n_experts:
        out, _ = mlp.moe_apply(params["moe"], m, _moe_cfg(cfg))
    else:
        out = mlp.apply(params["mlp"], m, _mlp_cfg(cfg))
    return x + out, cache


# ---------------------------------------------------------------------------
# Hybrid (zamba2) and xLSTM layer tables
# ---------------------------------------------------------------------------

def _hybrid_positions(cfg: ModelConfig) -> list[int]:
    """Mamba-layer indices after which the shared attn block runs."""
    if not cfg.shared_attn_every:
        return []
    return list(range(cfg.shared_attn_every - 1, cfg.n_layers,
                      cfg.shared_attn_every))


def _xlstm_kinds(cfg: ModelConfig) -> list[str]:
    if not cfg.slstm_every:
        return ["mlstm"] * cfg.n_layers
    return ["slstm" if (i + 1) % cfg.slstm_every == 0 else "mlstm"
            for i in range(cfg.n_layers)]


def _xlstm_segments(cfg: ModelConfig) -> list[tuple]:
    """[("m", lo, hi) | ("s", idx)] runs over the stacked param layout:
    consecutive mLSTM layers scan as one group."""
    kinds = _xlstm_kinds(cfg)
    segs: list[tuple] = []
    m_i = s_i = i = 0
    while i < len(kinds):
        if kinds[i] == "mlstm":
            lo = m_i
            while i < len(kinds) and kinds[i] == "mlstm":
                m_i += 1
                i += 1
            segs.append(("m", lo, m_i))
        else:
            segs.append(("s", s_i))
            s_i += 1
            i += 1
    return segs


# ---------------------------------------------------------------------------
# Model facade
# ---------------------------------------------------------------------------

class Batch(NamedTuple):
    """Inputs for train/prefill. ``embeds`` used by embeds-in stubs (audio)
    and VLM image prefixes; ``labels`` = -1 marks masked-out positions."""
    tokens: Array | None      # (b, s) int32 or None for embeds-in archs
    labels: Array             # (b, s) int32, -1 = ignore
    embeds: Array | None = None   # (b, s_img/s, d_model)


class DecodeBatch(NamedTuple):
    tokens: Array             # (b, 1) int32
    index: Array              # ()  current cache length


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.compute_dtype = _dtype(cfg.compute_dtype)

    # ----- specs -----

    def spec(self) -> dict:
        cfg = self.cfg
        s: dict[str, Any] = {}
        if not cfg.embeds_in:
            s["embed"] = common.embed_spec(cfg.vocab, cfg.d_model)
        s["final_norm"] = common.norm_spec(cfg.d_model, cfg.norm)
        s["unembed"] = common.unembed_spec(cfg.vocab, cfg.d_model)

        if cfg.family in ("dense", "moe", "encoder", "vlm"):
            layer = _tf_layer_spec(cfg)
            n = cfg.n_layers - cfg.first_dense_layers
            if cfg.first_dense_layers:
                s["dense_layers"] = [_tf_layer_spec(cfg, dense=True)
                                     for _ in range(cfg.first_dense_layers)]
            if cfg.scan_layers:
                s["layers"] = common.map_layers(layer, n)
            else:
                s["layers"] = [layer for _ in range(n)]
        elif cfg.family == "hybrid":
            mamba = ssm.spec(_ssm_cfg(cfg))
            s["layers"] = common.map_layers(mamba, cfg.n_layers)
            s["shared_attn"] = {
                "attn_norm": common.norm_spec(cfg.d_model, cfg.norm),
                "attn": attention.spec(_attn_cfg(cfg)),
                "mlp_norm": common.norm_spec(cfg.d_model, cfg.norm),
                "mlp": mlp.spec(_mlp_cfg(cfg)),
                "emb_proj": P((cfg.d_model, cfg.d_model),
                              ("embed", "embed")),
            }
        elif cfg.family == "ssm":  # xlstm
            xc = _xlstm_cfg(cfg)
            kinds = _xlstm_kinds(cfg)
            n_m = kinds.count("mlstm")
            n_s = kinds.count("slstm")
            s["layers"] = {
                "mlstm": common.map_layers(xlstm.mlstm_spec(xc), n_m)}
            if n_s:
                s["layers"]["slstm"] = common.map_layers(
                    xlstm.slstm_spec(xc), n_s)
        else:
            raise ValueError(cfg.family)
        return s

    def init(self, key: Array) -> dict:
        return common.init_params(key, self.spec(),
                                  _dtype(self.cfg.param_dtype))

    def abstract_params(self) -> dict:
        return common.abstract_params(self.spec(),
                                      _dtype(self.cfg.param_dtype))

    def param_shardings(self, mesh, rules=None) -> dict:
        return common.param_shardings(self.spec(), mesh, rules)

    # ----- forward (train / prefill) -----

    def _inputs_to_h(self, params: dict, batch: Batch) -> Array:
        cfg = self.cfg
        dt = self.compute_dtype
        if cfg.embeds_in:
            h = batch.embeds.astype(dt)
        else:
            h = common.embed(params["embed"], batch.tokens, dt)
            if cfg.family == "vlm" and batch.embeds is not None:
                img = shard(batch.embeds.astype(dt),
                            "act_batch", "act_seq", "act_embed")
                h = jnp.concatenate([img, h], axis=1)
        return h

    def forward(self, params: dict, batch: Batch) -> tuple[Array, Array]:
        """Returns (logits, moe_aux_loss)."""
        h, aux, _ = self._trunk(params, batch)
        logits = common.unembed(params["unembed"], h, self.compute_dtype)
        if self.cfg.family == "vlm" and batch.embeds is not None \
                and not self.cfg.embeds_in:
            logits = logits[:, batch.embeds.shape[1]:, :]   # text positions
        return logits, aux

    def last_logits(self, params: dict, batch: Batch
                    ) -> tuple[Array, Array]:
        """Logits of the last position only, ``(b, 1, vocab)``, and the
        held experts' pair counts per expert layer, ``(layers, n_held)``
        (``(layers, 0)`` for a model without them)."""
        h, _, loads = self._trunk(params, batch)
        return (common.unembed(params["unembed"], h[:, -1:],
                               self.compute_dtype), loads)

    def _trunk(self, params: dict, batch: Batch
               ) -> tuple[Array, Array, Array]:
        """Embed + layer stack + final norm -> (hidden, moe_aux, loads)."""
        cfg = self.cfg
        h = self._inputs_to_h(params, batch)

        # sequence-parallel residual stream: the per-layer remat checkpoint
        # (= the scan carry / layer input) is sharded along seq over "model"
        def resid(x):
            return shard(x, "act_batch", "act_resid_seq", "act_embed")

        h = resid(h)
        loads = jnp.zeros((0, 0), jnp.int32)
        if cfg.family in ("dense", "moe", "encoder", "vlm"):
            layer_fn = _remat(
                lambda p, x: _tf_layer(p, x, cfg), cfg)
            aux = jnp.float32(0.0)
            for lp in params.get("dense_layers", []):
                h, a, _ = layer_fn(lp, h)
                h = resid(h)
                aux = aux + a
            if cfg.scan_layers:
                def body(carry, layer_params):
                    x, aux = carry
                    x, a, n = layer_fn(layer_params, x)
                    return (resid(x), aux + a), n
                (h, aux), loads = jax.lax.scan(body, (h, aux),
                                               params["layers"])
            else:
                per_layer = []
                for lp in params["layers"]:
                    h, a, n = layer_fn(lp, h)
                    h = resid(h)
                    aux = aux + a
                    per_layer.append(n)
                loads = jnp.stack(per_layer)
        elif cfg.family == "hybrid":
            aux = jnp.float32(0.0)
            h = self._hybrid_forward(params, h)
        elif cfg.family == "ssm":
            aux = jnp.float32(0.0)
            xc = _xlstm_cfg(cfg)
            m_fn = _remat(lambda p, x: xlstm.mlstm_block(p, x, xc), cfg)
            s_fn = _remat(lambda p, x: xlstm.slstm_block(p, x, xc)[0], cfg)
            for seg in _xlstm_segments(cfg):
                if seg[0] == "m":     # consecutive mLSTM layers: one scan
                    _, lo, hi = seg
                    xs = jax.tree.map(lambda a: a[lo:hi],
                                      params["layers"]["mlstm"])

                    def body(x, lp):
                        return resid(m_fn(lp, x)), None

                    h, _ = jax.lax.scan(body, h, xs)
                else:
                    lp = jax.tree.map(lambda a: a[seg[1]],
                                      params["layers"]["slstm"])
                    h = resid(s_fn(lp, h))
        else:
            raise ValueError(cfg.family)

        h = common.apply_norm(h, params.get("final_norm"), cfg.norm,
                              cfg.norm_eps)
        return h, aux, loads

    def _hybrid_forward(self, params: dict, h: Array) -> Array:
        """Mamba backbone scanned in groups between shared-block stops.

        Grouped ``lax.scan`` keeps the HLO ~shared_attn_every-x smaller
        than a flat python loop (38 unrolled Mamba layers made GSPMD
        compile time explode)."""
        cfg = self.cfg
        scfg = _ssm_cfg(cfg)
        h0 = h  # original embeddings feed the shared block (zamba-style)
        mamba_fn = _remat(lambda p, x: x + ssm.apply(p, x, scfg), cfg)

        def resid(x):
            return shard(x, "act_batch", "act_resid_seq", "act_embed")

        def scan_group(h, lo, hi):
            xs = jax.tree.map(lambda a: a[lo:hi], params["layers"])

            def body(x, lp):
                return resid(mamba_fn(lp, x)), None

            h, _ = jax.lax.scan(body, h, xs)
            return h

        def shared_fn(p, x):
            inj = x + (h0 @ p["emb_proj"].astype(x.dtype))
            a = common.apply_norm(inj, p["attn_norm"], cfg.norm)
            x = x + attention.full(p["attn"], a, _attn_cfg(cfg))
            m = common.apply_norm(x, p["mlp_norm"], cfg.norm)
            return x + mlp.apply(p["mlp"], m, _mlp_cfg(cfg))

        shared_fn = _remat(shared_fn, cfg)
        k = cfg.shared_attn_every or cfg.n_layers
        lo = 0
        while lo < cfg.n_layers:
            hi = min(lo + k, cfg.n_layers)
            h = scan_group(h, lo, hi)
            if hi - lo == k and cfg.shared_attn_every:
                h = resid(shared_fn(params["shared_attn"], h))
            lo = hi
        return h

    # ----- loss / train -----

    #: seq-chunked cross entropy kicks in above this (seq x vocab) size
    _LOSS_CHUNK = 1024

    def loss(self, params: dict, batch: Batch) -> Array:
        """Next-token / masked NLL with *chunked* cross entropy: fp32
        logits never materialize for the full sequence — each seq chunk's
        logits are (re)computed inside a checkpointed block (forward and
        backward), capping the live loss buffer at (b, chunk, vocab)."""
        cfg = self.cfg
        h, aux, _ = self._trunk(params, batch)
        if cfg.family == "vlm" and batch.embeds is not None \
                and not cfg.embeds_in:
            h = h[:, batch.embeds.shape[1]:, :]
        labels = batch.labels
        s = h.shape[1]
        ch = self._LOSS_CHUNK

        def chunk_nll(hc, lc):
            logits = common.unembed(params["unembed"], hc,
                                    self.compute_dtype)
            logits = logits.astype(jnp.float32)
            mask = (lc >= 0).astype(jnp.float32)
            safe = jnp.maximum(lc, 0)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
            return ((logz - gold) * mask).sum(), mask.sum()

        if s <= ch or s % ch != 0 or cfg.vocab < 8192:
            nll, cnt = chunk_nll(h, labels)
        else:
            chunk_nll = jax.checkpoint(chunk_nll)
            nll = jnp.float32(0.0)
            cnt = jnp.float32(0.0)
            for i in range(s // ch):
                sl = slice(i * ch, (i + 1) * ch)
                n, c = chunk_nll(h[:, sl], labels[:, sl])
                nll, cnt = nll + n, cnt + c
        return nll / jnp.maximum(cnt, 1.0) + aux

    # ----- decode -----

    def decode_state_spec(self, batch: int, max_seq: int) -> Any:
        cfg = self.cfg
        acfg = _attn_cfg(cfg)
        if cfg.is_encoder:
            raise ValueError("encoder-only arch has no decode step")
        if cfg.kv_lora_rank or cfg.first_dense_layers:
            raise ValueError(f"{cfg.arch_id}: latent attention and leading "
                             "dense layers run full sequences only (no "
                             "decode cache)")
        if cfg.family in ("dense", "moe", "vlm"):
            one = attention.cache_spec(acfg, batch, max_seq)
            return attention.KVCache(
                jax.ShapeDtypeStruct((cfg.n_layers, *one.k.shape),
                                     one.k.dtype),
                jax.ShapeDtypeStruct((cfg.n_layers, *one.v.shape),
                                     one.v.dtype))
        if cfg.family == "hybrid":
            sspec = ssm.state_spec(_ssm_cfg(cfg), batch)
            n_inv = len(_hybrid_positions(cfg))
            one = attention.cache_spec(acfg, batch, max_seq)
            return {
                "mamba": ssm.SSMState(
                    jax.ShapeDtypeStruct((cfg.n_layers, *sspec.ssm.shape),
                                         sspec.ssm.dtype),
                    jax.ShapeDtypeStruct((cfg.n_layers, *sspec.conv.shape),
                                         sspec.conv.dtype)),
                "attn": attention.KVCache(
                    jax.ShapeDtypeStruct((n_inv, *one.k.shape), one.k.dtype),
                    jax.ShapeDtypeStruct((n_inv, *one.v.shape), one.v.dtype)),
            }
        if cfg.family == "ssm":
            xc = _xlstm_cfg(cfg)
            return [xlstm.slstm_state_spec(xc, batch)
                    if kind == "slstm" else xlstm.mlstm_state_spec(xc, batch)
                    for kind in _xlstm_kinds(cfg)]
        raise ValueError(cfg.family)

    def init_decode_state(self, batch: int, max_seq: int) -> Any:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.decode_state_spec(batch, max_seq))

    def decode_step(self, params: dict, state: Any, batch: DecodeBatch
                    ) -> tuple[Array, Any]:
        """One token for the whole stack -> (logits (b, 1, vocab), state)."""
        cfg = self.cfg
        dt = self.compute_dtype
        h = common.embed(params["embed"], batch.tokens, dt)
        index = batch.index

        if cfg.family in ("dense", "moe", "vlm"):
            def body(x, inp):
                lp, k_l, v_l = inp
                x, cache = _tf_layer_decode(
                    lp, x, attention.KVCache(k_l, v_l), index, cfg)
                return x, (cache.k, cache.v)

            if cfg.scan_layers:
                h, (ks, vs) = jax.lax.scan(
                    body, h, (params["layers"], state.k, state.v))
                state = attention.KVCache(ks, vs)
            else:
                ks, vs = [], []
                for i, lp in enumerate(params["layers"]):
                    h, (k_l, v_l) = body(h, (lp, state.k[i], state.v[i]))
                    ks.append(k_l)
                    vs.append(v_l)
                state = attention.KVCache(jnp.stack(ks), jnp.stack(vs))
        elif cfg.family == "hybrid":
            h, state = self._hybrid_decode(params, h, state, index)
        elif cfg.family == "ssm":
            xc = _xlstm_cfg(cfg)
            new_states = []
            m_i = s_i = 0
            for kind, st in zip(_xlstm_kinds(cfg), state):
                if kind == "slstm":
                    lp = jax.tree.map(lambda a: a[s_i],
                                      params["layers"]["slstm"])
                    h, st = xlstm.slstm_block_step(lp, h, st, xc)
                    s_i += 1
                else:
                    lp = jax.tree.map(lambda a: a[m_i],
                                      params["layers"]["mlstm"])
                    h, st = xlstm.mlstm_block_step(lp, h, st, xc)
                    m_i += 1
                new_states.append(st)
            state = new_states
        else:
            raise ValueError(cfg.family)

        h = common.apply_norm(h, params.get("final_norm"), cfg.norm)
        logits = common.unembed(params["unembed"], h, dt)
        return logits, state

    def _hybrid_decode(self, params, h, state, index):
        cfg = self.cfg
        scfg = _ssm_cfg(cfg)
        shared_at = _hybrid_positions(cfg)
        h0 = h
        new_ssm, new_conv = [], []
        attn_k, attn_v = [], []
        inv = 0
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            st = ssm.SSMState(state["mamba"].ssm[i], state["mamba"].conv[i])
            out, st = ssm.decode_step(lp, h, st, scfg)
            h = h + out
            new_ssm.append(st.ssm)
            new_conv.append(st.conv)
            if i in shared_at:
                p = params["shared_attn"]
                inj = h + (h0 @ p["emb_proj"].astype(h.dtype))
                a = common.apply_norm(inj, p["attn_norm"], cfg.norm)
                cache = attention.KVCache(state["attn"].k[inv],
                                          state["attn"].v[inv])
                attn_out, cache = attention.decode_step(
                    p["attn"], a, cache, index, _attn_cfg(cfg))
                h = h + attn_out
                m = common.apply_norm(h, p["mlp_norm"], cfg.norm)
                h = h + mlp.apply(p["mlp"], m, _mlp_cfg(cfg))
                attn_k.append(cache.k)
                attn_v.append(cache.v)
                inv += 1
        state = {
            "mamba": ssm.SSMState(jnp.stack(new_ssm), jnp.stack(new_conv)),
            "attn": attention.KVCache(jnp.stack(attn_k), jnp.stack(attn_v)),
        }
        return h, state


def build(cfg: ModelConfig) -> Model:
    return Model(cfg)
