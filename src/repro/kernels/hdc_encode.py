"""Pallas TPU kernel: fused HDC RFF encoding.

``phi(x) = cos(xB + b) * sin(xB)`` as a single tiled matmul with the
nonlinearity fused into the epilogue — the projection never round-trips to
HBM. Grid: ``(N/bn, D/bd, K/bk)`` with the K axis as the innermost
(sequential) reduction; accumulation is kept in an fp32 VMEM scratch and the
epilogue fires on the last K step.

Block shapes are MXU-aligned (multiples of 128 on the N/D axes; the
reduction axis ``bk`` is a VMEM-footprint knob). VMEM working set per step:
``bn*bk + bk*bd + 2*bn*bd`` floats.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.encoding import NonLin
from jax.experimental.pallas.tpu import CompilerParams


def _encode_kernel(x_ref, b_mat_ref, bias_ref, o_ref, acc_ref, *,
                   nonlinearity: NonLin, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        b_mat_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        proj = acc_ref[...]
        bias = bias_ref[...].astype(jnp.float32)  # (1, bd)
        if nonlinearity == "rff":
            out = jnp.cos(proj + bias) * jnp.sin(proj)
        elif nonlinearity == "sign":
            out = jnp.sign(proj)
        else:  # linear
            out = proj
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("nonlinearity", "block_n", "block_d", "block_k",
                     "interpret"),
)
def hdc_encode(x: jax.Array, B: jax.Array, b: jax.Array, *,
               nonlinearity: NonLin = "rff", block_n: int = 128,
               block_d: int = 512, block_k: int = 512,
               interpret: bool = False) -> jax.Array:
    """Fused encode: ``(N, K) @ (K, D)`` + pointwise nonlinearity.

    Pads every axis up to its block multiple (masked out on the way back).
    """
    n, k = x.shape
    k2, d = B.shape
    assert k == k2, (x.shape, B.shape)
    bn = min(block_n, max(8, n))
    bd = min(block_d, d)
    bk = min(block_k, k)

    def pad_to(a, axis, mult):
        size = a.shape[axis]
        rem = (-size) % mult
        if rem == 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, rem)
        return jnp.pad(a, widths)

    xp = pad_to(pad_to(x, 0, bn), 1, bk)
    Bp = pad_to(pad_to(B, 0, bk), 1, bd)
    biasp = pad_to(b.reshape(1, -1), 1, bd)
    n_p, k_p = xp.shape
    _, d_p = Bp.shape
    n_k = k_p // bk

    out = pl.pallas_call(
        functools.partial(_encode_kernel, nonlinearity=nonlinearity,
                          n_k=n_k),
        grid=(n_p // bn, d_p // bd, n_k),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bd), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bd), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_p, d_p), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bd), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, Bp, biasp)
    return out[:n, :d]
