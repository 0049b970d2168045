"""Feed-forward blocks: SwiGLU dense MLP and top-k MoE.

MoE uses capacity-bounded sort-based dispatch (GShard-style capacity, but
scatter/gather instead of the O(N*E*C) one-hot einsum): tokens are ranked
within their assigned expert via an argsort; tokens beyond expert capacity
are dropped (standard). Experts shard over the "model" mesh axis (EP) —
with tokens sharded over "data", XLA inserts the all-to-all at the
dispatch/return boundaries.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models.common import P

Array = jax.Array


# ---------------------------------------------------------------------------
# Dense SwiGLU
# ---------------------------------------------------------------------------

class MLPConfig(NamedTuple):
    d_model: int
    d_ff: int
    activation: str = "silu"     # silu (llama family) | gelu (encoders)
    gated: bool = True


def spec(cfg: MLPConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    s = {"w_up": P((d, f), ("embed", "mlp")),
         "w_down": P((f, d), ("mlp", "embed"))}
    if cfg.gated:
        s["w_gate"] = P((d, f), ("embed", "mlp"))
    return s


def _act(x: Array, kind: str) -> Array:
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x)


def apply(params: dict, x: Array, cfg: MLPConfig) -> Array:
    dt = x.dtype
    up = x @ params["w_up"].astype(dt)
    up = shard(up, "act_batch", "act_seq", "act_mlp")
    if cfg.gated:
        gate = _act(x @ params["w_gate"].astype(dt), cfg.activation)
        h = gate * up
    else:
        h = _act(up, cfg.activation)
    out = h @ params["w_down"].astype(dt)
    return shard(out, "act_batch", "act_seq", "act_embed")


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------

class MoEConfig(NamedTuple):
    d_model: int
    d_ff: int                 # per-expert hidden
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    activation: str = "silu"
    router_aux_weight: float = 0.01
    dispatch_int8: bool = False   # quantize the EP dispatch gather payload


def moe_spec(cfg: MoEConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": P((d, e), ("embed", "expert")),
        "w_gate": P((e, d, f), ("expert", "embed", "expert_mlp")),
        "w_up": P((e, d, f), ("expert", "embed", "expert_mlp")),
        "w_down": P((e, f, d), ("expert", "expert_mlp", "embed")),
    }


def _capacity(n_tokens: int, cfg: MoEConfig) -> int:
    cap = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(cap, cfg.top_k)


def moe_apply(params: dict, x: Array, cfg: MoEConfig
              ) -> tuple[Array, Array]:
    """(b, s, d) -> ((b, s, d), aux_loss).

    Sort-based capacity dispatch:
      1. router softmax -> top-k (expert, weight) per token
      2. rank tokens within each expert (argsort by expert id)
      3. scatter into (E, C, d) buffers (drop beyond capacity)
      4. batched expert SwiGLU: (E, C, d) x (E, d, f)
      5. weighted scatter-add back to token positions
    """
    b, s, d = x.shape
    n = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(n, cfg)
    dt = x.dtype
    xf = x.reshape(n, d)

    # --- route ---
    logits = (xf.astype(jnp.float32)
              @ params["router"].astype(jnp.float32))           # (n, e)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_e = jax.lax.top_k(probs, k)                    # (n, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch): E * sum(frac_tokens * frac_prob)
    me = jnp.mean(probs, axis=0)                                # (e,)
    ce = jnp.mean(jax.nn.one_hot(gate_e[:, 0], e), axis=0)
    aux = cfg.router_aux_weight * e * jnp.sum(me * ce)

    # --- rank within expert ---
    flat_e = gate_e.reshape(-1)                                 # (n*k,)
    sort_idx = jnp.argsort(flat_e, stable=True)                 # (n*k,)
    sorted_e = flat_e[sort_idx]
    counts = jnp.bincount(flat_e, length=e)                     # (e,)
    offsets = jnp.cumsum(counts) - counts                       # (e,)
    pos_sorted = jnp.arange(n * k) - offsets[sorted_e]          # rank in expert
    pos = jnp.zeros(n * k, jnp.int32).at[sort_idx].set(
        pos_sorted.astype(jnp.int32))                           # (n*k,)
    keep = pos < cap

    # --- dispatch: scatter token INDICES (int32), then one row-gather ---
    # Scattering indices instead of activation rows keeps the cross-shard
    # payload at N*d (one all-gather of the token matrix) instead of
    # N*k*d (k copies of every token) — an 8x collective reduction for
    # top-8 routing (§Perf hillclimb C2).
    tok_idx = jnp.repeat(jnp.arange(n), k)                      # (n*k,)
    dest_e = jnp.where(keep, flat_e, e)         # overflow -> dropped row
    dest_c = jnp.where(keep, pos, 0)
    idx_buf = jnp.full((e + 1, cap), n, jnp.int32)  # n = zero-row sentinel
    idx_buf = idx_buf.at[dest_e, dest_c].set(tok_idx.astype(jnp.int32),
                                             mode="drop")
    if cfg.dispatch_int8:
        # int8-quantize the token matrix so the cross-shard dispatch
        # gather moves 2x less than bf16 (4x less than f32); per-token
        # symmetric scales ride along (n x 4 bytes). Expert MLPs tolerate
        # the ~1/127 relative error (§Perf hillclimb C6).
        scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                            1e-6).astype(jnp.float32) / 127.0
        xq = jnp.clip(jnp.round(xf.astype(jnp.float32) / scale),
                      -127, 127).astype(jnp.int8)
        xq_pad = jnp.concatenate([xq, jnp.zeros((1, d), jnp.int8)], axis=0)
        sc_pad = jnp.concatenate([scale, jnp.ones((1, 1), jnp.float32)],
                                 axis=0)
        buf = (xq_pad[idx_buf[:e]].astype(jnp.float32)
               * sc_pad[idx_buf[:e]]).astype(dt)                # (e, cap, d)
    else:
        xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), dt)], axis=0)
        buf = xf_pad[idx_buf[:e]]                               # (e, cap, d)
    buf = shard(buf, "act_expert", "act_expert_cap", None)

    # --- expert compute (batched over experts) ---
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf,
                                  params["w_gate"].astype(dt))) \
        if cfg.activation == "silu" else \
        jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf,
                               params["w_gate"].astype(dt)))
    up = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(dt))
    hidden = shard(gate * up, "act_expert", "act_expert_cap", None)
    out_buf = jnp.einsum("ecf,efd->ecd", hidden,
                         params["w_down"].astype(dt))           # (e, cap, d)

    # --- return: gather expert outputs back to tokens, weighted combine ---
    flat_w = gate_w.reshape(-1).astype(dt)                      # (n*k,)
    expert_out = out_buf[dest_e.clip(0, e - 1), dest_c]         # (n*k, d)
    expert_out = jnp.where((keep & (dest_e < e))[:, None], expert_out, 0)
    combined = jnp.zeros((n, d), dt).at[tok_idx].add(
        expert_out * flat_w[:, None])
    out = combined.reshape(b, s, d)
    return shard(out, "act_batch", "act_seq", "act_embed"), aux


# ---------------------------------------------------------------------------
# Dropless expert layer over held experts (expert parallelism's local part)
# ---------------------------------------------------------------------------

class HeldExpertsConfig(NamedTuple):
    d_model: int
    d_ff: int                 # per-expert hidden
    n_held: int               # experts held here
    n_routed: int             # the router's width
    top_k: int
    offset: int = 0           # router index of the first held expert
    n_shared: int = 0         # shared experts, one SwiGLU n_shared*d_ff wide
    scaling: float = 1.0


def held_spec(cfg: HeldExpertsConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_held
    s = {
        "router": P((d, cfg.n_routed), ("embed", None)),
        "router_bias": P((cfg.n_routed,), (None,), "zeros"),
        "w_gate": P((e, d, f), ("expert", "embed", "expert_mlp")),
        "w_up": P((e, d, f), ("expert", "embed", "expert_mlp")),
        "w_down": P((e, f, d), ("expert", "expert_mlp", "embed")),
    }
    if cfg.n_shared:
        s["shared"] = spec(MLPConfig(d, cfg.n_shared * f))
    return s


def route(params: dict, xf: Array, cfg: HeldExpertsConfig
          ) -> tuple[Array, Array]:
    """(n, d) -> the top-k ``(router index, weight)`` of each token.

    Logits over every routed expert in float32, scored with a sigmoid; the
    choice is by score plus ``router_bias`` (the correction bias of aux-free
    balancing), the weight the score alone, always renormalised over the k
    chosen and scaled.
    """
    logits = jnp.dot(xf.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choice = scores + params["router_bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(choice, cfg.top_k)                 # (n, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg.scaling


def held_apply(params: dict, x: Array, cfg: HeldExpertsConfig
               ) -> tuple[Array, Array]:
    """(..., d) -> ((..., d), (n_held,) int32 pairs per held expert).

    Routes over all ``n_routed`` experts and computes the part of the
    result the held ones give, dropping no token: the (token, expert)
    pairs whose expert is held are sorted by expert and run through a
    grouped matmul (``jax.lax.ragged_dot``), so the work follows the
    routed pairs. Pairs routed elsewhere contribute nothing here. The
    shared experts run on every token.
    """
    shape, d = x.shape, x.shape[-1]
    dt = x.dtype
    xf = x.reshape(-1, d)
    n, k, e = xf.shape[0], cfg.top_k, cfg.n_held
    idx, w = route(params, xf, cfg)
    local = idx - cfg.offset
    held = (local >= 0) & (local < e)                          # (n, k)
    key = jnp.where(held, local, e).reshape(-1)                # e: not here
    order = jnp.argsort(key, stable=True)                      # held first
    loads = jnp.sum(key[:, None] == jnp.arange(e), axis=0,
                    dtype=jnp.int32)                           # (e,)
    xs = jnp.take(xf, order // k, axis=0)                      # (n*k, d)
    gate = jax.lax.ragged_dot(xs, params["w_gate"].astype(dt), loads)
    up = jax.lax.ragged_dot(xs, params["w_up"].astype(dt), loads)
    ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up,
                            params["w_down"].astype(dt), loads)
    # back to (token, choice) order; rows past the held pairs are not
    # written by the grouped matmul, so mask rather than weight them
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=order.dtype))
    y = jnp.take(ys, inv, axis=0).reshape(n, k, d).astype(jnp.float32)
    y = jnp.where(held[..., None], y * w[..., None], 0.0).sum(axis=1)
    out = y.astype(dt).reshape(shape)
    if cfg.n_shared:
        out = out + apply(params["shared"], x,
                          MLPConfig(d, cfg.n_shared * cfg.d_ff))
    return out, loads
