"""Shared model substrate: param specs, norms, embeddings, RoPE.

Param definition uses a tiny single-source-of-truth spec system: every
parameter is declared once as :class:`P` (shape + logical sharding axes +
init); materialization (:func:`init_params`), abstract shapes
(:func:`abstract_params`) and shardings (:func:`param_shardings`) all
derive from the same spec — they cannot drift apart.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.distributed import sharding as shlib

Array = jax.Array


class P(NamedTuple):
    """Declaration of one parameter tensor."""
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"            # normal | zeros | ones
    scale: float | None = None      # stddev; default fan-in

    def with_layers(self, n_layers: int) -> "P":
        """Prefix a scan-stacked ``layers`` dim."""
        return P((n_layers, *self.shape), ("layers", *self.axes),
                 self.init, self.scale)


SpecTree = Any  # nested dict[str, P]


def map_layers(spec: SpecTree, n_layers: int) -> SpecTree:
    return jax.tree.map(lambda p: p.with_layers(n_layers), spec,
                        is_leaf=lambda x: isinstance(x, P))


def init_params(key: Array, spec: SpecTree,
                dtype: jnp.dtype = jnp.float32) -> dict:
    leaves, treedef = jax.tree.flatten(spec,
                                       is_leaf=lambda x: isinstance(x, P))
    keys = jax.random.split(key, len(leaves))

    def one(k, p: P):
        if p.init == "zeros":
            return jnp.zeros(p.shape, dtype)
        if p.init == "ones":
            return jnp.ones(p.shape, dtype)
        fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
        scale = p.scale if p.scale is not None else fan_in ** -0.5
        return (scale * jax.random.normal(k, p.shape)).astype(dtype)

    return jax.tree.unflatten(treedef, [one(k, p)
                                        for k, p in zip(keys, leaves)])


def abstract_params(spec: SpecTree,
                    dtype: jnp.dtype = jnp.float32) -> dict:
    """ShapeDtypeStruct tree (for ``.lower()`` without allocation)."""
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype), spec,
        is_leaf=lambda x: isinstance(x, P))


def param_shardings(spec: SpecTree, mesh: Mesh,
                    rules: dict | None = None) -> dict:
    """NamedSharding tree from the declared logical axes."""
    return jax.tree.map(
        lambda p: shlib.logical_sharding(p.shape, p.axes, mesh, rules),
        spec, is_leaf=lambda x: isinstance(x, P))


def abstract_like(tree, mesh: Mesh | None = None, spec: SpecTree | None = None):
    """ShapeDtypeStruct tree with shardings attached (dry-run inputs)."""
    del mesh, spec
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def count_params(tree) -> int:
    sizes = [int(jnp.size(x)) if hasattr(x, "size") else 0
             for x in jax.tree.leaves(tree)]
    return sum(sizes)


def spec_param_count(spec: SpecTree) -> int:
    import math
    leaves = jax.tree.leaves(spec, is_leaf=lambda x: isinstance(x, P))
    return sum(math.prod(p.shape) for p in leaves)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: Array, weight: Array | None, eps: float = 1e-6) -> Array:
    """fp32 statistics, but no full fp32 activation copy: the upcast is
    consumed only by the variance reduction (fuses away), so no f32
    activation tensor exists to be gathered/reduced across shards
    (§Perf hillclimb C7)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    out = x * inv
    if weight is not None:
        out = out * weight.astype(x.dtype)
    return out


def layer_norm(x: Array, weight: Array | None = None,
               bias: Array | None = None, eps: float = 1e-5) -> Array:
    """Non-parametric when weight/bias are None (OLMo's LN).

    Same dtype discipline as :func:`rms_norm`: fp32 statistics only."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    out = (x - mu.astype(x.dtype)) * inv
    if weight is not None:
        out = out * weight.astype(x.dtype)
    if bias is not None:
        out = out + bias.astype(x.dtype)
    return out


def apply_norm(x: Array, params: dict | None, kind: str,
               eps: float = 0.0) -> Array:
    """``eps`` 0 takes the norm's own default (1e-6 RMS, 1e-5 layer)."""
    if kind == "rmsnorm":
        return rms_norm(x, params["scale"] if params else None, eps or 1e-6)
    if kind == "layernorm":
        return layer_norm(x, params["scale"] if params else None,
                          params.get("bias") if params else None,
                          eps or 1e-5)
    if kind == "nonparametric_ln":
        return layer_norm(x, None, None, eps or 1e-5)
    raise ValueError(kind)


def norm_spec(d: int, kind: str) -> dict:
    if kind == "rmsnorm":
        return {"scale": P((d,), ("norm",), "ones")}
    if kind == "layernorm":
        return {"scale": P((d,), ("norm",), "ones"),
                "bias": P((d,), ("norm",), "zeros")}
    if kind == "nonparametric_ln":
        return {}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """x: (..., S, H, head_dim); positions: (..., S)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                    # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_spec(vocab: int, d: int) -> dict:
    return {"embedding": P((vocab, d), ("vocab", "embed"), "normal", 0.02)}


def embed(params: dict, tokens: Array, compute_dtype) -> Array:
    emb = params["embedding"].astype(compute_dtype)
    out = jnp.take(emb, tokens, axis=0)
    return shlib.shard(out, "act_batch", "act_seq", "act_embed")


def unembed_spec(vocab: int, d: int) -> dict:
    return {"kernel": P((d, vocab), ("embed", "vocab"))}


def unembed(params: dict, x: Array, compute_dtype) -> Array:
    logits = x.astype(compute_dtype) @ params["kernel"].astype(compute_dtype)
    return shlib.shard(logits, "act_batch", "act_seq", "act_vocab")
