"""Pallas TPU kernel: fused computation-reuse HyperSense frame scoring.

This is the paper's FPGA accelerator (§IV) adapted to TPU (DESIGN.md §3).
One kernel maps a sensor frame directly to the fragment score map:

  frame (H, W)  ->  scores-ingredients (my, mx) x 3

fusing, per grid cell (one frame x one hyperdimension tile):

  1. *projection reuse on the MXU* — for each row band ``ky`` the band's
     ``h`` rows are contracted against the circularly padded base slab in
     ONE matmul, ``P[i, q] = sum_r x[r, i] * slab[r, q]``: every input
     element meets the base material exactly once per base row (the
     paper's computation reuse). Aligning row ``i`` left by its frame
     column turns ``P`` into the per-column rolled products
     ``G[i, j] = P[i, i + j]``. Where every band chunk is a full
     :data:`W_CHUNK` columns of unpacked input (:func:`strided_alignment`)
     the frame's columns arrive reversed within each chunk and ONE
     sublane-strided lane rotate of the lane-aligned product does it
     (:func:`_rotate_to_diagonals`); otherwise a log-step roll-and-select
     per bit of the largest shift (:func:`_rows_to_diagonals`).
  2. *window sums* — every fragment's projection is the sum of the ``w``
     rows of ``G`` its window covers (the reuse of overlapping fragments).
  3. *normalization + RFF nonlinearity* — in the *unrolled* orientation:
     instead of cyclically rotating every (mx, D) projection back (the
     naive inverse of the permutation trick), the per-column *bias* and
     *class hypervectors* are pre-rotated once per model. A (D,)-vector
     rotation per fragment column, amortized over every frame forever,
     replaces an (mx, D) data rotation per frame — a beyond-paper
     optimization available because similarity is permutation-invariant.
  4. *classifier dot products* — positive/negative class dots and the query
     sum-of-squares per D tile; the tiles fold and the cosine epilogue
     runs outside the kernel on the tiny (my, mx) outputs.

The same kernel body serves the integer datapath
(:mod:`repro.kernels.sliding_scores_int`): integer codes take the int8
MXU path of :func:`_project` with exact int32 accumulation, float frames
the f32 one.

Grid: ``(N, n_dt)`` — frames and hyperdimension tiles, both parallel;
the row bands loop inside the kernel, so the frame block is fetched once
per frame. The batch axis is the streaming hot path: one ``pallas_call``
scores a whole chunk of frames against a single :class:`ScoreTiles`
precompute (slabs/bias/class tiles are per-model, not per-frame). VMEM per
step: frame (H, W) + slab (h, :func:`slab_width`) + bias/class tiles
(mx, TD) + the ``(W_CHUNK, TD+W_CHUNK)`` projection of one band chunk —
independent of N.

A hypervector dimensionality the tile width does not divide (the paper's
D=5000) is padded up to whole lane-aligned tiles; the padded tail's
``valid`` mask zeroes its contribution, so scores are those of the true D.

``fragment_scores`` (single frame) is a batch-of-1 call into the same
kernel; ``fragment_scores_batch`` is the chunked entry point used by
``repro.sensing.stream``.

Precomputation is split along the *mutability* boundary of the model
(online learning — paper §I "real-time learning"):

* :class:`ScoreGeometry` — the expensive, class-independent part: circularly
  padded base slabs, the pre-rotated RFF bias tiles, and the rotation
  gather ``idx`` itself. Depends only on ``(B0, b, W, w, stride, block_d)``;
  computed host-side once per (model-geometry, frame-width) by
  :func:`precompute_geometry`.
* class tiles — the cheap, class-*dependent* part: the pre-rotated
  positive/negative class hypervector tiles plus their L2 norms. Produced
  from a geometry by the **jitted, device-side** :func:`retile_classes`:
  one gather per class through the stored ``idx`` plus two norms. Updating
  the classifier mid-stream (the online-learning hot path) costs a
  ``retile_classes`` call — never a host-side re-precompute.

:class:`ScoreTiles` = geometry + class tiles; :func:`precompute_tiles`
(the historical all-in-one entry point) is now exactly
``retile_classes(precompute_geometry(...), class_hvs)``.

For fleets adapting a *per-stream* classifier, ``fragment_scores_batch``
accepts class tiles with a leading stream axis (``frames_per_stream``):
the kernel grid is unchanged, but the class-tile BlockSpec index maps pick
stream ``n // C``'s tiles for batch element ``n`` — still ONE launch.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.encoding import SHIFT, NonLin, apply_nonlinearity

Array = jax.Array

#: static W-axis chunk of the projection: bounds the per-band product
#: ``(W_CHUNK, TD + W_CHUNK - 1)`` independent of the frame width
W_CHUNK = 128

#: lane width of a TPU vector register: padded tile widths are multiples
_LANES = 128


def _round_lanes(n: int) -> int:
    return -(-n // _LANES) * _LANES


def slab_width(td: int, W: int) -> int:
    """Columns of one D-tile's base slab: a lane multiple ``>= td + W``.

    Band chunk ``c0`` reads ``[c0, c0 + L)`` with ``L`` the lane multiple
    ``>= td + cw``, which the strided alignment needs; the log-step one
    reads the first ``td + cw - 1`` of them. Columns past ``td + W - 1``
    are cyclic base columns that no kept lane reads.
    """
    return _round_lanes(td + W)


def strided_alignment(W: int, *, packed: bool = False) -> bool:
    """Whether the kernel aligns its band products with one strided
    lane rotate (:func:`_rotate_to_diagonals`) rather than the log-step
    roll-and-select (:func:`_rows_to_diagonals`).

    Needs every band chunk to be a full :data:`W_CHUNK` columns of one
    code or value each: the product is then ``(W_CHUNK, L)`` with ``L``
    a lane multiple, which the TPU rotates in one op. Packed int4 bytes
    (two column groups of stride 2) and frame widths that leave a partial
    chunk take the log-step path.
    """
    return not packed and W % W_CHUNK == 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ScoreGeometry:
    """Class-independent kernel precompute (see module docstring).

    Depends only on ``(B0, b)`` and the frame geometry — *not* on the class
    hypervectors, so it survives every online-learning model update. The
    stored rotation gather ``idx`` is what makes class updates cheap:
    re-tiling a new classifier is one gather through it per class.
    """
    slabs: Array      # (n_dt, h, slab_width(TD, W)) circular base rows
    bias_t: Array     # (n_dt, mx, TD) pre-rotated RFF bias tiles
    idx: Array        # (n_dt, mx, TD) i32 rotation gather into a (D,) vector
    valid: Array      # (n_dt, 1, TD) f32: 1 on real components, 0 on padding
    block_d: int = dataclasses.field(metadata={"static": True})
    w: int = dataclasses.field(metadata={"static": True})
    stride: int = dataclasses.field(metadata={"static": True})


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ScoreTiles:
    """Geometry + class-dependent tiles: the full kernel input bundle.

    ``cpos_t``/``cneg_t`` are ``(n_dt, mx, TD)`` for a single shared
    classifier, or ``(S, n_dt, mx, TD)`` (with ``(S,)`` norms) for a fleet
    adapting per-stream classifiers (see :func:`fragment_scores_batch`).
    """
    geom: ScoreGeometry
    cpos_t: Array     # ([S,] n_dt, mx, TD) pre-rotated positive class tiles
    cneg_t: Array     # ([S,] n_dt, mx, TD) pre-rotated negative class tiles
    cpos_norm: Array  # ([S]) L2 of positive class hypervector
    cneg_norm: Array  # ([S]) L2 of negative class hypervector

    # Back-compat passthroughs (pre-split callers read these off the tiles).
    @property
    def slabs(self) -> Array:
        return self.geom.slabs

    @property
    def bias_t(self) -> Array:
        return self.geom.bias_t

    @property
    def block_d(self) -> int:
        return self.geom.block_d

    @property
    def w(self) -> int:
        return self.geom.w

    @property
    def stride(self) -> int:
        return self.geom.stride


def tile_layout(dim: int, block_d: int) -> tuple[int, int]:
    """``(TD, n_dt)``: tile width and tile count covering ``dim``.

    ``block_d`` tiles when it divides ``dim``; otherwise ``dim`` is padded
    up to whole tiles of ``min(block_d, dim rounded up to a lane
    multiple)`` — never one ``dim``-wide tile, whose width the TPU's
    128-lane registers cannot tile and whose slab outgrows VMEM.
    """
    if dim % block_d == 0:
        return block_d, dim // block_d
    td = min(block_d, _round_lanes(dim))
    return td, -(-dim // td)


def precompute_geometry(B0: Array, b: Array, *, W: int, w: int, stride: int,
                        block_d: int = 512) -> ScoreGeometry:
    """Host-side, once per (model-geometry, frame-width): slabs + bias + idx.

    The expensive precompute. Everything class-dependent is deferred to
    :func:`retile_classes` so the classifier can change without re-running
    this. Padded components (``dim`` not a multiple of the tile) read
    real, cyclically wrapped values and are zeroed by ``valid``.
    """
    h, dim = B0.shape
    assert SHIFT == -1, "precompute assumes the paper's left-shift"
    td, n_dt = tile_layout(dim, block_d)
    mx = (W - w) // stride + 1

    # slab column q of tile dt is base column (dt*TD + q) % D: the cyclic
    # shift every fragment column needs, wrapped past the end of B0
    cols = (jnp.arange(n_dt)[:, None] * td
            + jnp.arange(slab_width(td, W))[None, :]) % dim
    slabs = jnp.moveaxis(B0[:, cols], 1, 0)           # (n_dt, h, slab_width)

    # idx[dt, kx, j] = (dt*TD + j + kx*stride) % D   (rotation by fragment col)
    dts = jnp.arange(n_dt)[:, None, None] * td
    kxs = jnp.arange(mx)[None, :, None] * stride
    js = jnp.arange(td)[None, None, :]
    idx = (dts + js + kxs) % dim                            # (n_dt, mx, TD)
    comp = jnp.arange(n_dt * td).reshape(n_dt, 1, td)
    return ScoreGeometry(
        slabs=slabs.astype(jnp.float32),
        bias_t=b[idx].astype(jnp.float32),
        idx=idx,
        valid=(comp < dim).astype(jnp.float32),
        block_d=td,
        w=w,
        stride=stride,
    )


@jax.jit
def retile_classes(geom: ScoreGeometry, class_hvs: Array) -> ScoreTiles:
    """Device-side classifier (re-)tiling: ``(2, D)`` -> :class:`ScoreTiles`.

    One gather per class through the stored rotation ``idx`` plus two norms
    — the entire cost of installing an updated classifier into the scoring
    kernel. Jitted: safe to call inside a larger jitted streaming step
    (the online-adaptation hot path) as well as standalone.

    ``vmap`` over ``class_hvs`` (``(S, 2, D)``) yields the per-stream tile
    stack the fleet's per-stream adaptation mode consumes.
    """
    cpos = class_hvs[1].astype(jnp.float32)
    cneg = class_hvs[0].astype(jnp.float32)
    return ScoreTiles(
        geom=geom,
        cpos_t=cpos[geom.idx],
        cneg_t=cneg[geom.idx],
        cpos_norm=jnp.linalg.norm(cpos),
        cneg_norm=jnp.linalg.norm(cneg),
    )


@jax.jit
def retile_classes_fleet(geom: ScoreGeometry, class_hvs: Array) -> ScoreTiles:
    """Per-stream classifier tiling: ``(S, 2, D)`` -> stacked tiles.

    The geometry stays shared (un-batched); only the class tiles and norms
    grow a leading stream axis, ready for
    ``fragment_scores_batch(..., frames_per_stream=C)``.
    """
    cpos = class_hvs[:, 1].astype(jnp.float32)               # (S, D)
    cneg = class_hvs[:, 0].astype(jnp.float32)
    return ScoreTiles(
        geom=geom,
        cpos_t=jax.vmap(lambda v: v[geom.idx])(cpos),        # (S,n_dt,mx,TD)
        cneg_t=jax.vmap(lambda v: v[geom.idx])(cneg),
        cpos_norm=jnp.linalg.norm(cpos, axis=-1),            # (S,)
        cneg_norm=jnp.linalg.norm(cneg, axis=-1),
    )


def precompute_tiles(B0: Array, b: Array, class_hvs: Array, *, W: int,
                     w: int, stride: int, block_d: int = 512) -> ScoreTiles:
    """Host-side, once per (model, frame-width): geometry + class tiles.

    The historical all-in-one entry point; now literally the composition
    ``retile_classes(precompute_geometry(...), class_hvs)``.
    """
    geom = precompute_geometry(B0, b, W=W, w=w, stride=stride,
                               block_d=block_d)
    return retile_classes(geom, class_hvs)


def window_norms(frame: Array, h: int, w: int, stride: int) -> Array:
    """(my, mx) L2 norms of every sliding window via a summed-area table."""
    H, W = frame.shape
    my = (H - h) // stride + 1
    mx = (W - w) // stride + 1
    f = frame.astype(jnp.float32)
    sq = jnp.cumsum(jnp.cumsum(f * f, axis=0), axis=1)
    sq = jnp.pad(sq, ((1, 0), (1, 0)))
    ky = jnp.arange(my) * stride
    kx = jnp.arange(mx) * stride
    win = (sq[ky[:, None] + h, kx[None, :] + w]
           - sq[ky[:, None] + h, kx[None, :]]
           - sq[ky[:, None], kx[None, :] + w]
           + sq[ky[:, None], kx[None, :]])
    return jnp.sqrt(jnp.maximum(win, 1e-16))


def window_norms_batch(frames: Array, h: int, w: int, stride: int) -> Array:
    """(N, my, mx) sliding-window L2 norms for a stack of frames."""
    return jax.vmap(lambda f: window_norms(f, h, w, stride))(frames)


def _project(cols: Array, slab: Array, *, planes: int) -> Array:
    """``P[l, q] = sum_r cols[r, l] * slab[r, q]`` — one MXU contraction.

    ``planes == 0``: float operands, f32 accumulation at full precision.
    ``planes >= 1``: non-negative integer codes, split into ``planes``
    bytes. The MXU multiplies int8, so each byte is offset by -128 and the
    offset is added back as ``128 * colsum(slab)`` — exact int32
    arithmetic, whatever the byte values.
    """
    dims = (((0,), (0,)), ((), ()))
    if planes == 0:
        return jax.lax.dot_general(cols, slab, dims,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
    offset = 128 * jnp.sum(slab.astype(jnp.int32), axis=0, keepdims=True)
    out = None
    for k in range(planes):
        byte = cols if planes == 1 else jnp.right_shift(cols, 8 * k) & 0xFF
        part = jax.lax.dot_general((byte - 128).astype(jnp.int8), slab, dims,
                                   preferred_element_type=jnp.int32) + offset
        out = part if out is None else out + jnp.left_shift(part, 8 * k)
    return out


def _rows_to_diagonals(p: Array, shift: Array, *, max_shift: int,
                       td: int) -> Array:
    """``g[l, j] = p[l, shift[l] + j]`` for ``j < td``, by rolling.

    The log-step alignment, for any shape: one roll+select pass per bit
    of ``max_shift`` aligns row ``l`` left by ``shift[l]`` (composition
    of circular rolls is the roll by the sum); ``shift[l] + j <=
    max_shift + td - 1 < p.shape[1]``, so no wrapped element is ever
    kept. The kernel uses it where :func:`strided_alignment` is false,
    the jnp int twin always; it is the oracle of
    :func:`_rotate_to_diagonals`.
    """
    bit = 1
    while bit <= max_shift:
        rolled = jnp.concatenate([p[:, bit:], p[:, :bit]], axis=1)
        p = jnp.where((shift & bit) != 0, rolled, p)
        bit *= 2
    return p[:, :td]


def _rotate_to_diagonals(p: Array, *, td: int) -> Array:
    """``g[i, j] = p[i, (n - 1 - i) + j]`` for ``j < td``: one rotate.

    ``p`` is ``(n, L)`` with ``L`` a lane multiple, and its rows are the
    band chunk's frame columns in reverse, so row ``i`` needs a LEFT
    alignment by ``n - 1 - i``. ``pltpu.roll`` with a sublane stride
    rotates row ``i`` RIGHT by ``shift + i``, which is the left rotation
    by ``n - 1 - i`` when ``shift = L - (n - 1)``. As in the log-step
    path, ``n - 1 + td - 1 < L`` keeps every wrapped lane out of ``g``.
    Only values move: bitwise the log-step result of the unreversed rows.
    """
    n, L = p.shape
    return pltpu.roll(p, L - (n - 1), 1, stride=1, stride_axis=0)[:, :td]


def _window_sums(g: Array, col: Array, *, first: int, last: int, w: int,
                 stride: int, mx: int) -> Array:
    """``(mx, TD)``: per fragment column, the sum of the rows of ``g``
    whose frame column ``col`` lies in its window. Windows that miss the
    static column range ``[first, last]`` of ``g`` contribute zeros."""
    rows = []
    for kx in range(mx):
        lo = kx * stride
        if lo + w <= first or lo > last:
            rows.append(jnp.zeros((1, g.shape[1]), g.dtype))
            continue
        inside = (col >= lo) & (col < lo + w)
        rows.append(jnp.sum(jnp.where(inside, g, 0), axis=0, keepdims=True))
    return jnp.concatenate(rows, axis=0)


def _window_acc(band: Array, slabs: Array, *, W: int, td: int, w: int,
                stride: int, mx: int, packed: bool = False,
                strided: bool = False) -> Array:
    """One row band ``(h, W)`` -> its ``(mx, TD)`` fragment projections.

    The paper's computation reuse with an O(window) live set: summing over
    base rows commutes with shift extraction, so ONE matmul per band chunk
    (:func:`_project`) multiplies each input element once per base row,
    the alignment turns it into the per-column rolled sums, and
    :func:`_window_sums` aggregates every fragment. The ``W`` axis is
    chunked statically (:data:`W_CHUNK`) so the scratch stays bounded.
    Float bands accumulate in f32; integer codes (``< 2**16``; uint8 in
    one byte plane) exactly in int32. ``packed`` bands are the int4 wire
    format ``(h, W/2)``: low nibbles are the even columns, high nibbles
    the odd ones, projected separately — nothing is interleaved.
    ``strided`` bands hold each chunk's columns in reverse
    (:func:`_reverse_chunks`) and align by one strided rotate
    (:func:`strided_alignment` says where that applies).
    """
    if jnp.issubdtype(band.dtype, jnp.integer):
        planes = 1 if band.dtype.itemsize == 1 else 2
        band = band.astype(jnp.int32)
    else:
        planes = 0
    acc = None
    for c0 in range(0, W, W_CHUNK):
        cw = min(W_CHUNK, W - c0)
        # (first column offset, column step, byte planes) per column group
        groups = ((0, 2, 1), (1, 2, 1)) if packed else ((0, 1, planes),)
        for first, step, n_planes in groups:
            if packed:
                byte = band[:, c0 // 2:(c0 + cw) // 2]
                cols = byte & 0xF if first == 0 else jnp.right_shift(byte, 4)
            else:
                cols = band[:, c0:c0 + cw]
            last = first + step * (cols.shape[1] - 1)
            if strided:
                p = _project(cols, slabs[:, c0:c0 + _round_lanes(td + cw)],
                             planes=n_planes)
                g = _rotate_to_diagonals(p, td=td)
                # row i holds frame column c0 + cw - 1 - i
                col = c0 + cw - 1 - jax.lax.broadcasted_iota(
                    jnp.int32, g.shape, 0)
            else:
                p = _project(cols, slabs[:, c0:c0 + td + cw - 1],
                             planes=n_planes)
                shift = first + step * jax.lax.broadcasted_iota(
                    jnp.int32, p.shape, 0)
                g = _rows_to_diagonals(p, shift, max_shift=last, td=td)
                col = c0 + first + step * jax.lax.broadcasted_iota(
                    jnp.int32, g.shape, 0)
            part = _window_sums(g, col, first=c0 + first, last=c0 + last,
                                w=w, stride=stride, mx=mx)
            acc = part if acc is None else acc + part
    return acc


def _reverse_chunks(x: Array) -> Array:
    """Reverse the last axis within each :data:`W_CHUNK` block: the input
    layout of the strided alignment (one XLA pass, outside the kernel)."""
    *lead, W = x.shape
    return jnp.flip(x.reshape(*lead, W // W_CHUNK, W_CHUNK),
                    axis=-1).reshape(x.shape)


def _score_kernel(x_ref, slab_ref, bias_ref, valid_ref, cpos_ref, cneg_ref,
                  norm_ref, dpos_ref, dneg_ref, qq_ref, *, h: int, w: int,
                  stride: int, W: int, my: int, mx: int, td: int,
                  nonlinearity: NonLin, packed: bool, strided: bool):
    slabs = slab_ref[0]                                      # (h, slab_width)
    bias = bias_ref[0]                                       # (mx, TD)
    valid = valid_ref[0]                                     # (1, TD)
    cpos = cpos_ref[0].astype(jnp.float32)
    cneg = cneg_ref[0].astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (my, mx), 0)

    def band(ky, outs):
        x = x_ref[0, pl.ds(ky * stride, h), :]               # (h, W[/2])
        acc = _window_acc(x, slabs, W=W, td=td, w=w, stride=stride, mx=mx,
                          packed=packed, strided=strided)    # (mx, TD)
        norms = norm_ref[0, pl.ds(ky, 1), :]                 # (1, mx)
        s_n = acc.astype(jnp.float32) / norms.T
        # the ONE nonlinearity definition (repro.core.encoding), shared
        # with the jnp oracles; padded components are zeroed by `valid`
        phi = apply_nonlinearity(s_n, bias, nonlinearity) * valid
        sums = (jnp.sum(phi * cpos, axis=1), jnp.sum(phi * cneg, axis=1),
                jnp.sum(phi * phi, axis=1))
        return tuple(jnp.where(row == ky, v[None, :], o)
                     for v, o in zip(sums, outs))

    zero = jnp.zeros((my, mx), jnp.float32)
    dpos, dneg, qq = jax.lax.fori_loop(0, my, band, (zero, zero, zero))
    # Per-tile partial sums, one (my, mx) block per (D-tile, frame). The
    # tiles are reduced OUTSIDE the kernel by _ordered_tile_fold so the
    # combine order is a fixed left-to-right fold regardless of how the
    # n_dt axis is sharded across devices — the basis of the bitwise
    # sharded == unsharded guarantee (see fragment_scores_batch).
    dpos_ref[0, 0] = dpos
    dneg_ref[0, 0] = dneg
    qq_ref[0, 0] = qq


def _ordered_tile_fold(parts: Array,
                       hyperdim_axes: tuple[str, ...] | None = None) -> Array:
    """Reduce a leading D-tile axis with a FIXED left-to-right fold.

    ``parts`` is ``(n_dt_local, ...)`` per-tile partial sums. When the
    tile axis is sharded over mesh axes ``hyperdim_axes``, a tiled
    ``all_gather`` first restores the *global* tile order, so every mesh
    shape folds the exact same floats in the exact same order and the
    result is bitwise-identical to the single-device reduction. A plain
    ``jnp.sum``/``psum`` would let XLA reassociate the adds and break
    that guarantee — do not "simplify" this into one.
    """
    with jax.named_scope("tile_fold"):
        if hyperdim_axes:
            parts = jax.lax.all_gather(parts, hyperdim_axes, axis=0,
                                       tiled=True)
        out = parts[0]
        for i in range(1, parts.shape[0]):
            out = out + parts[i]
        return out


def _cosine_epilogue(dpos, dneg, qq, tiles, per_stream: bool, C: int):
    """Folded dots -> ``sim(pos) - sim(neg)``; per-stream class norms
    broadcast over that stream's ``C`` frames."""
    with jax.named_scope("cosine_epilogue"):
        qn = jnp.maximum(jnp.sqrt(qq), 1e-9)
        if per_stream:
            rep = lambda v: jnp.repeat(v, C)[:, None, None]   # (N, 1, 1)
            return (dpos / (qn * jnp.maximum(rep(tiles.cpos_norm), 1e-9))
                    - dneg / (qn * jnp.maximum(rep(tiles.cneg_norm),
                                               1e-9)))
        return (dpos / (qn * jnp.maximum(tiles.cpos_norm, 1e-9))
                - dneg / (qn * jnp.maximum(tiles.cneg_norm, 1e-9)))


def scores_from_tiles(x: Array, slabs: Array, tiles, norms: Array, *,
                      h: int, w: int, stride: int, W: int,
                      nonlinearity: NonLin, interpret: bool,
                      frames_per_stream: int | None, packed: bool,
                      hyperdim_axes: tuple[str, ...] | None) -> Array:
    """The one ``pallas_call`` behind the float and integer entry points.

    ``x`` is ``(N, H, W)`` frames or integer codes (``(N, H, W/2)`` bytes
    when ``packed``), ``slabs`` the geometry's (float or int8) slabs,
    ``norms`` the ``(N, my, mx)`` window norms the projections divide by.
    Where :func:`strided_alignment` holds, ``x``'s columns are reversed
    within each band chunk here, before the launch; ``norms`` come from
    the frames as they are.
    """
    geom = tiles.geom
    N, H, Wx = x.shape
    n_dt, h_b, slab_len = slabs.shape
    td = geom.block_d
    my, mx = norms.shape[1:]
    # repro-lint: disable=RA001 (td is a static aux field of the tile pytree — concrete at trace time)
    assert h_b == h and slab_len == slab_width(td, W), (slabs.shape, td, W)
    strided = strided_alignment(W, packed=packed)
    if strided:
        x = _reverse_chunks(x)

    per_stream = tiles.cpos_t.ndim == 4
    C = 0
    if per_stream:
        if frames_per_stream is None:
            raise ValueError("per-stream class tiles need frames_per_stream")
        C = frames_per_stream
        S = tiles.cpos_t.shape[0]
        if S * C != N:
            raise ValueError(f"per-stream tiles: S={S} streams x "
                             f"C={C} frames != batch N={N}")
        # (S, n_dt, mx, td) -> (S*n_dt, mx, td): batch n reads stream n//C.
        cpos_t = tiles.cpos_t.reshape(S * n_dt, mx, td)
        cneg_t = tiles.cneg_t.reshape(S * n_dt, mx, td)
        class_spec = pl.BlockSpec(
            (1, mx, td), lambda n, j: ((n // C) * n_dt + j, 0, 0))
    else:
        cpos_t, cneg_t = tiles.cpos_t, tiles.cneg_t
        class_spec = pl.BlockSpec((1, mx, td), lambda n, j: (j, 0, 0))

    kern = functools.partial(
        _score_kernel, h=h, w=w, stride=stride, W=W, my=my, mx=mx, td=td,
        nonlinearity=nonlinearity, packed=packed, strided=strided)
    tile = lambda n, j: (j, 0, 0)
    dpos, dneg, qq = pl.pallas_call(
        kern,
        grid=(N, n_dt),
        in_specs=[
            pl.BlockSpec((1, H, Wx), lambda n, j: (n, 0, 0)),      # frame
            pl.BlockSpec((1, h, slab_len), tile),                  # slabs
            pl.BlockSpec((1, mx, td), tile),                       # bias
            pl.BlockSpec((1, 1, td), tile),                        # valid
            class_spec,                                            # cpos
            class_spec,                                            # cneg
            pl.BlockSpec((1, my, mx), lambda n, j: (n, 0, 0)),     # norms
        ],
        out_specs=[pl.BlockSpec((1, 1, my, mx),
                                lambda n, j: (j, n, 0, 0))] * 3,
        out_shape=[jax.ShapeDtypeStruct((n_dt, N, my, mx),
                                        jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="hypersense_scores",
    )(x, slabs, geom.bias_t, geom.valid, cpos_t, cneg_t, norms)

    dpos = _ordered_tile_fold(dpos, hyperdim_axes)
    dneg = _ordered_tile_fold(dneg, hyperdim_axes)
    qq = _ordered_tile_fold(qq, hyperdim_axes)
    return _cosine_epilogue(dpos, dneg, qq, tiles, per_stream, C)


@functools.partial(jax.jit, static_argnames=("h", "w", "stride",
                                             "nonlinearity", "interpret",
                                             "frames_per_stream",
                                             "hyperdim_axes"))
def fragment_scores_batch(frames: Array, tiles: ScoreTiles, *, h: int,
                          w: int, stride: int,
                          nonlinearity: NonLin = "rff",
                          interpret: bool = False,
                          frames_per_stream: int | None = None,
                          hyperdim_axes: tuple[str, ...] | None = None
                          ) -> Array:
    """(N, H, W) frames -> (N, my, mx) score maps in one kernel launch.

    The whole batch shares one :class:`ScoreGeometry` precompute; the
    Pallas grid is ``(N, n_dt)``, both axes parallel. Each D-tile emits
    its own partial dot products; the tiles are folded outside the kernel
    in fixed left-to-right order (bitwise-stable).

    Inside a ``shard_map`` whose mesh partitions the tile axis over
    ``hyperdim_axes``, pass those axis names: ``tiles`` then holds this
    device's contiguous D-shard (``n_dt_local`` leading dim) and the fold
    is preceded by one tiled ``all_gather`` over the hyperdim axis — the
    single collective the D-sharded epilogue needs. Scores stay
    bitwise-identical to the unsharded launch for every mesh shape.

    With shared class tiles (``tiles.cpos_t.ndim == 3``) every frame is
    scored against the same classifier. With *per-stream* class tiles
    (``(S, n_dt, mx, TD)``, from ``vmap(retile_classes)``) the batch is
    interpreted as S streams of ``frames_per_stream`` frames each (must be
    static and divide N): batch element ``n`` reads stream ``n // C``'s
    class tiles via the BlockSpec index map — same grid, same kernel body,
    still ONE launch. That is the fleet's per-stream online-learning path.
    """
    W = frames.shape[-1]
    assert tiles.w == w and tiles.stride == stride  # repro-lint: disable=RA001 (static aux fields of the tile pytree)
    norms = jnp.maximum(window_norms_batch(frames, h, w, stride), 1e-8)
    return scores_from_tiles(
        frames, tiles.slabs, tiles, norms, h=h, w=w, stride=stride, W=W,
        nonlinearity=nonlinearity, interpret=interpret,
        frames_per_stream=frames_per_stream, packed=False,
        hyperdim_axes=hyperdim_axes)


def fragment_scores(frame: Array, tiles: ScoreTiles, *, h: int, w: int,
                    stride: int, nonlinearity: NonLin = "rff",
                    interpret: bool = False) -> Array:
    """Frame -> (my, mx) fragment score map (sim(pos) - sim(neg))."""
    return fragment_scores_batch(frame[None], tiles, h=h, w=w,
                                 stride=stride, nonlinearity=nonlinearity,
                                 interpret=interpret)[0]
