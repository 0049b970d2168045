"""Pallas TPU kernel: MXU HDC encoding with in-VMEM permutation expansion.

The beyond-paper optimization for TPU (§Perf cell 3, EXPERIMENTS.md):

The paper's computation reuse saves *multiplies* — the right currency on
an FPGA. On TPU the MXU is ~50x denser than the VPU, so recomputing the
multiplies as a plain matmul beats the prefix-sum reuse. What the
permutation structure (Eq. 1) is *still* worth on TPU is **memory**: the
full base matrix ``B (h*w, D)`` (184 MB at the paper's operating point)
is generated from only the ``h`` generator rows ``B0 (h, D)`` (1.9 MB),
so this kernel keeps B0 resident in VMEM and materializes each MXU tile
of B on the fly — base HBM traffic drops by ``w`` (96x), turning the
memory-bound naive matmul into a compute-bound one at MXU speed.

Layout: fragments ``(N, h*w)`` row-major (row r, column j) -> flat index
``r*w + j`` pairs with ``B[r*w + j] = roll(B0[r], j*SHIFT)``. For an MXU
K-tile covering flat rows [k0, k0+bk) and a D-tile [d0, d0+bd), row
``r*w + j`` needs ``B0P[r, d0 + j : d0 + j + bd]`` — a dynamic slice of
the circularly padded generators. The kernel builds the (bk, bd) tile
with a ``fori_loop`` of row slices, then issues ``jnp.dot``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.encoding import SHIFT, NonLin
from jax.experimental.pallas.tpu import CompilerParams


def _kernel(x_ref, b0p_ref, bias_ref, o_ref, acc_ref, btile_ref, *,
            nonlinearity: NonLin, n_k: int, bk: int, bd: int, w: int,
            dim: int):
    kk = pl.program_id(2)
    jd = pl.program_id(1)
    d0 = jd * bd

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # build the (bk, bd) base tile from the generators (VMEM-local)
    def row_body(i, _):
        flat = kk * bk + i
        r = flat // w
        j = flat % w
        # roll(B0[r], j*SHIFT)[d0:d0+bd] = B0P[r, d0+j : d0+j+bd] (SHIFT=-1)
        assert SHIFT == -1
        start = (d0 + j) % dim
        seg = b0p_ref[pl.ds(r, 1), pl.ds(start, bd)]
        btile_ref[pl.ds(i, 1), :] = seg.astype(btile_ref.dtype)
        return 0

    jax.lax.fori_loop(0, bk, row_body, 0)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        btile_ref[...],
        preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _epilogue():
        proj = acc_ref[...]
        bias = bias_ref[...].astype(jnp.float32)
        if nonlinearity == "rff":
            out = jnp.cos(proj + bias) * jnp.sin(proj)
        elif nonlinearity == "sign":
            out = jnp.sign(proj)
        else:
            out = proj
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("h", "w", "nonlinearity",
                                             "block_n", "block_d",
                                             "block_k", "interpret"))
def hdc_encode_perm(x: jax.Array, B0: jax.Array, b: jax.Array, *, h: int,
                    w: int, nonlinearity: NonLin = "rff",
                    block_n: int = 128, block_d: int = 512,
                    block_k: int = 256, interpret: bool = False
                    ) -> jax.Array:
    """Encode flattened fragments ``(N, h*w)`` against the
    permutation-structured base generated from ``B0 (h, D)``.

    Equivalent to ``hdc_encode(x, flat_perm_base(B0, w), b)`` but the
    expanded base never exists outside VMEM tiles.
    """
    n, k = x.shape
    assert k == h * w, (x.shape, h, w)
    dim = B0.shape[1]
    bn = min(block_n, max(8, n))
    bd = min(block_d, dim)
    bk = min(block_k, k)
    assert k % bk == 0, "h*w must divide block_k after clamping"
    assert dim % bd == 0, (dim, bd)

    def pad_to(a, axis, mult):
        rem = (-a.shape[axis]) % mult
        if rem == 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, rem)
        return jnp.pad(a, widths)

    xp = pad_to(x, 0, bn)
    n_p = xp.shape[0]
    n_k = k // bk
    # circular pad so every (d0 + j, bd) slice is contiguous
    B0P = jnp.concatenate([B0, B0[:, :bd + w]], axis=1)
    biasp = b.reshape(1, -1)

    out = pl.pallas_call(
        functools.partial(_kernel, nonlinearity=nonlinearity, n_k=n_k,
                          bk=bk, bd=bd, w=w, dim=dim),
        grid=(n_p // bn, dim // bd, n_k),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec(B0P.shape, lambda i, j, kk: (0, 0)),  # resident
            pl.BlockSpec((1, bd), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_p, dim), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bd), jnp.float32),
                        pltpu.VMEM((bk, bd), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, B0P, biasp)
    return out[:n]
