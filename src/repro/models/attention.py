"""Grouped-query attention: full (train/prefill) + cached decode step;
multi-head latent attention (MLA, deepseek_v3): full sequence only.

Covers every assigned transformer family: MHA (kv=heads), GQA (kv<heads),
causal and bidirectional, optional QK-norm (Qwen3), RoPE.

Sharding: head dims carry the "heads"/"kv_heads" logical axes -> tensor
parallel over the "model" mesh axis; the KV cache shards batch over
("pod","data") and kv_heads over "model" when divisible.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models import common
from repro.models.common import P

Array = jax.Array


class AttnConfig(NamedTuple):
    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    causal: bool = True
    rope_theta: float = 10000.0
    qk_norm: bool = False
    norm: str = "rmsnorm"
    q_chunk: int = 1024   # query-block size: caps the live score buffer


def spec(cfg: AttnConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    s = {
        "wq": P((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": P((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": P((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": P((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        s["q_norm"] = common.norm_spec(hd, cfg.norm)
        s["k_norm"] = common.norm_spec(hd, cfg.norm)
    return s


def _project_qkv(params: dict, x: Array, cfg: AttnConfig, positions: Array):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.qk_norm:
        q = common.apply_norm(q, params["q_norm"], cfg.norm)
        k = common.apply_norm(k, params["k_norm"], cfg.norm)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "act_batch", "act_seq", "act_heads", None)
    k = shard(k, "act_batch", "act_seq", "act_kv_heads", None)
    v = shard(v, "act_batch", "act_seq", "act_kv_heads", None)
    return q, k, v


def _sdpa_block(q: Array, k: Array, v: Array, cfg: AttnConfig,
                q_positions: Array, k_positions: Array,
                k_mask: Array | None = None) -> Array:
    """One query block: (b, sq, h, hd) x (b, sk, kv, hd) -> (b, sq, h, hd).

    Scores are materialized with the (kv, group) dims merged so the full
    head dim (h = kv*group) can claim the "model" mesh axis even when
    kv_heads alone doesn't divide it.
    """
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    group = h // kv
    qg = q.reshape(b, sq, kv, group, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(hd)
    sk = k.shape[1]
    scores = scores.reshape(b, h, sq, sk)
    scores = shard(scores, "act_batch", "act_heads", None, None)
    neg = jnp.finfo(jnp.float32).min
    if cfg.causal:
        causal = q_positions[:, None] >= k_positions[None, :]   # (sq, sk)
        scores = jnp.where(causal[None, None, :, :], scores, neg)
    if k_mask is not None:                                      # (b, sk)
        scores = jnp.where(k_mask[:, None, None, :], scores, neg)
    attn = jax.nn.softmax(scores, axis=-1)
    attn = attn.reshape(b, kv, group, sq, sk)
    out = jnp.einsum("bkgqs,bskh->bqkgh", attn, v.astype(jnp.float32))
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def _sdpa(q: Array, k: Array, v: Array, cfg: AttnConfig,
          q_positions: Array, k_positions: Array,
          k_mask: Array | None = None) -> Array:
    """Query-chunked attention: the live score buffer is capped at
    (b, h, q_chunk, sk) — flash-style blocking without the online-softmax
    pass (each query row still sees all keys, so per-block softmax is
    exact). Python loop, not lax.scan: keeps HLO cost analysis exact and
    lets XLA pipeline blocks."""
    sq = q.shape[1]
    qc = cfg.q_chunk
    if sq <= qc:
        return _sdpa_block(q, k, v, cfg, q_positions, k_positions, k_mask)
    outs = []
    for lo in range(0, sq, qc):
        hi = min(lo + qc, sq)       # ragged tail allowed (e.g. VLM prefix)
        sl = slice(lo, hi)
        # causal: skip key blocks that are entirely masked for this
        # query block (the flash-attention triangle-skipping trick)
        k_end = min(hi, k.shape[1]) if cfg.causal else k.shape[1]
        outs.append(_sdpa_block(
            q[:, sl], k[:, :k_end], v[:, :k_end], cfg, q_positions[sl],
            k_positions[:k_end],
            None if k_mask is None else k_mask[:, :k_end]))
    return jnp.concatenate(outs, axis=1)


def full(params: dict, x: Array, cfg: AttnConfig,
         positions: Array | None = None) -> Array:
    """Training / prefill attention over the whole sequence."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _project_qkv(params, x, cfg, positions)
    out = _sdpa(q, k, v, cfg, positions, positions)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return shard(out, "act_batch", "act_seq", "act_embed")


# ---------------------------------------------------------------------------
# Multi-head latent attention (full sequence)
# ---------------------------------------------------------------------------

class MLAConfig(NamedTuple):
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    causal: bool = True
    rope_theta: float = 10000.0
    q_chunk: int = 1024


def mla_spec(cfg: MLAConfig) -> dict:
    """``wq`` gives each head its nope and rope query dims (no query
    latent); ``wkv_a`` the latent and the one rotary key all heads share;
    ``wkv_b`` expands the normed latent to each head's key-nope and value
    dims."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {
        "wq": P((d, h, qk), ("embed", "heads", "head_dim")),
        "wkv_a": P((d, r + cfg.qk_rope_head_dim), ("embed", None)),
        "kv_norm": common.norm_spec(r, "rmsnorm"),
        "wkv_b": P((r, h, cfg.qk_nope_head_dim + cfg.v_head_dim),
                   (None, "heads", "head_dim")),
        "wo": P((h, cfg.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


def _rope_pairs(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding on dims laid out as interleaved pairs (the
    published deepseek_v3 layout): de-interleave to halves, then rotate.
    The output keeps the halves layout, the same for queries and keys."""
    halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return common.apply_rope(halves, positions, theta)


def mla_full(params: dict, x: Array, cfg: MLAConfig,
             positions: Array | None = None) -> Array:
    """(b, s, d) -> (b, s, d): latent attention over the whole sequence.

    The latent is RMS-normed (eps 1e-6, as published) and expanded to
    per-head keys and values; scores are scaled by (nope + rope)^-1/2.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    dt = x.dtype
    nope, rope, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    ckv = x @ params["wkv_a"].astype(dt)                        # (b,s,r+rope)
    latent = common.rms_norm(ckv[..., :r], params["kv_norm"]["scale"])
    kv = jnp.einsum("bsr,rhk->bshk", latent, params["wkv_b"].astype(dt))
    q_pe = _rope_pairs(q[..., nope:], positions, cfg.rope_theta)
    k_pe = _rope_pairs(ckv[:, :, None, r:], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe, (b, s, cfg.n_heads, rope))], axis=-1)
    q = shard(q, "act_batch", "act_seq", "act_heads", None)
    k = shard(k, "act_batch", "act_seq", "act_heads", None)
    v = shard(kv[..., nope:], "act_batch", "act_seq", "act_heads", None)
    acfg = AttnConfig(d_model=cfg.d_model, n_heads=cfg.n_heads,
                      kv_heads=cfg.n_heads, head_dim=nope + rope,
                      causal=cfg.causal, q_chunk=cfg.q_chunk)
    out = _sdpa(q, k, v, acfg, positions, positions)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return shard(out, "act_batch", "act_seq", "act_embed")


class KVCache(NamedTuple):
    """Decode-time cache: pre-filled keys/values + current length."""
    k: Array        # (b, max_s, kv, hd)
    v: Array        # (b, max_s, kv, hd)


def cache_spec(cfg: AttnConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, max_seq, cfg.kv_heads, cfg.head_dim)
    return KVCache(jax.ShapeDtypeStruct(shape, dtype),
                   jax.ShapeDtypeStruct(shape, dtype))


def cache_axes() -> KVCache:
    # "cache_seq" (not "act_seq"): for archs whose kv_heads don't divide the
    # model axis, the rules shard the cache along sequence instead (the
    # taken-set resolution in repro.distributed.sharding picks whichever
    # dim divides; attention softmax then reduces over the model axis).
    ax = ("act_batch", "cache_seq", "act_kv_heads", None)
    return KVCache(ax, ax)


def init_cache(cfg: AttnConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, max_seq, cfg.kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def decode_step(params: dict, x: Array, cache: KVCache, index: Array,
                cfg: AttnConfig) -> tuple[Array, KVCache]:
    """One-token decode: x (b, 1, d); cache holds ``index`` valid tokens."""
    b = x.shape[0]
    positions = jnp.full((1,), index, dtype=jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, cfg, positions)
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new.astype(cache.k.dtype),
                                            index, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new.astype(cache.v.dtype),
                                            index, axis=1)
    k = shard(k, "act_batch", "cache_seq", "act_kv_heads", None)
    v = shard(v, "act_batch", "cache_seq", "act_kv_heads", None)
    max_s = k.shape[1]
    k_positions = jnp.arange(max_s)
    valid = (k_positions <= index)[None, :].repeat(b, 0)      # (b, max_s)
    out = _sdpa(q, k.astype(q.dtype), v.astype(q.dtype), cfg,
                positions, k_positions, k_mask=valid)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    out = shard(out, "act_batch", "act_seq", "act_embed")
    return out, KVCache(k, v)
