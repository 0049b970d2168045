"""Pallas TPU kernel: fused cosine-similarity classifier.

``scores = normalize(Q) @ normalize(C)^T`` for query hypervectors
``Q (N, D)`` against class hypervectors ``C (C, D)``.

Fusion: query normalization (rsqrt of a row-reduction) happens in-kernel so
the normalized queries never hit HBM. The class matrix is tiny (C=2 for
HyperSense) and is loaded whole; class norms are folded in-kernel too.
Grid: ``(N/bn, D/bd)`` with D the sequential reduction axis — both the dot
products and the query sum-of-squares accumulate across D steps, and the
epilogue divides on the last step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.experimental.pallas.tpu import CompilerParams


def _sim_kernel(q_ref, c_ref, o_ref, dots_ref, qq_ref, cc_ref, *, n_d: int,
                eps: float):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        dots_ref[...] = jnp.zeros_like(dots_ref)
        qq_ref[...] = jnp.zeros_like(qq_ref)
        cc_ref[...] = jnp.zeros_like(cc_ref)

    q = q_ref[...].astype(jnp.float32)            # (bn, bd)
    c = c_ref[...].astype(jnp.float32)            # (C, bd)
    dots_ref[...] += jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (bn, C)
    qq_ref[...] += jnp.sum(q * q, axis=-1, keepdims=True)   # (bn, 1)
    cc_ref[...] += jnp.sum(c * c, axis=-1, keepdims=True).T  # (1, C)

    @pl.when(pl.program_id(1) == n_d - 1)
    def _epilogue():
        qn = jnp.maximum(jnp.sqrt(qq_ref[...]), eps)         # (bn, 1)
        cn = jnp.maximum(jnp.sqrt(cc_ref[...]), eps)         # (1, C)
        o_ref[...] = (dots_ref[...] / (qn * cn)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_d",
                                             "interpret"))
def similarity(queries: jax.Array, class_hvs: jax.Array, *,
               block_n: int = 256, block_d: int = 1024,
               interpret: bool = False, eps: float = 1e-9) -> jax.Array:
    """Cosine class scores ``(N, D), (C, D) -> (N, C)`` in fp32."""
    n, d = queries.shape
    c, d2 = class_hvs.shape
    assert d == d2
    bn = min(block_n, max(8, n))
    bd = min(block_d, d)

    def pad_to(a, axis, mult):
        rem = (-a.shape[axis]) % mult
        if rem == 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, rem)
        return jnp.pad(a, widths)

    qp = pad_to(pad_to(queries, 0, bn), 1, bd)
    cp = pad_to(class_hvs, 1, bd)
    n_p, d_p = qp.shape
    n_d = d_p // bd

    out = pl.pallas_call(
        functools.partial(_sim_kernel, n_d=n_d, eps=eps),
        grid=(n_p // bn, n_d),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((c, bd), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, c), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_p, c), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bn, c), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((1, c), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, cp)
    return out[:n]
