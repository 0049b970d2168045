"""Pallas TPU kernel: low-precision integer HyperSense frame scoring.

The float path (:mod:`repro.kernels.sliding_scores`) consumes the ADC's
*reconstruction* ``codes * LSB`` — every kernel still does float32 work, so
the "energy-efficient low-precision ADC" of the paper buys nothing past the
converter. This module is the paper's actual FPGA datapath (§IV) brought to
the kernel level, following the SCM always-on HDC accelerator (Eggimann et
al., 2021) and the low-bitwidth hypervector-design line (Basaklar et al.,
2021): the raw integer **ADC codes** flow into the scoring kernel untouched,
every fragment projection accumulates in **int32**, and floats appear only
in the tiny similarity/normalization epilogue.

Why the integer path is *structurally* different (not just a dtype swap):

* **In-kernel rolling shifts over base slabs.** The float kernel walks each
  frame row with an ``O(h*(W+mx))``-step scalar prefix-sum loop (the
  systolic FIFO in loop form). The int kernel instead stores only the
  int8-quantized *base* slabs — the same circularly padded
  ``(n_dt, h, slab_width(TD, W))`` rows the float geometry keeps — and
  materializes every shifted view **inside** the grid step: one int8 MXU
  matmul ``codesᵀ (W, h) @ slabs (h, ~TD + W)`` with int32
  accumulation folds the ``h`` reused rolled products per column (summing
  over base rows *before* the shift is valid because shift extraction is
  linear; codes are offset by -128 into int8 and the offset added back
  exactly), then one strided lane rotate (unpacked codes in whole
  128-column chunks) or ``log2(W)`` vectorized roll+select passes align
  row ``i`` by its column so the per-column rolled sums ``G (W, TD)``
  fall out as diagonals, and each fragment window sums its ``w`` rows of
  ``G``. This is the float kernel's body
  (:mod:`repro.kernels.sliding_scores`) on integer operands — one kernel
  serves every precision. The live set is
  ``O(window)`` in ``W`` — base slabs + a bounded per-chunk scratch —
  never the old all-``W`` pre-expanded ``(h*W, TD)`` operand whose VMEM
  footprint grew linearly in ``W`` and overran the budget exactly at
  deployment scale (h=16, W=4096, TD=512 -> 32 MB/tile; the new layout is
  ~100 KB of slabs). :func:`assert_int_datapath_fits` enforces the bound,
  and ``tests/test_workingset.py`` pins the regression: the expanded
  layout's byte count sits *over* the budget at large ``W`` while this
  layout stays under it.
* **Sub-byte precisions.** ``packed=True`` consumes the int4 wire format
  (two 4-bit codes per byte, :func:`repro.sensing.adc.pack_nibbles`) and
  splits nibbles in-kernel — low nibbles are the even columns, high
  nibbles the odd ones, each projected on its own — halved code traffic,
  int32 accumulation unchanged. ``mode="binary"`` geometry sign-quantizes
  slabs to ±1 (scale = mean |slab|, the L2-optimal 1-bit approximation)
  and class HVs to ±1
  (norm ``sqrt(D)``): the XOR-popcount similarity of binarized HDC
  expressed as the same int8 matmuls, enabling reduced-D operating points
  (D-vs-AUC curve reported by ``benchmarks/int_datapath.py``).
* **LSB cancellation.** The fragment projection is normalized by the
  window's L2 norm, so the ADC step size cancels:
  ``(LSB * acc) / (LSB * ||codes||) = acc / ||codes||``. Scores from the
  int path live on the same scale as the float path — ``t_score``
  thresholds and ROC sweeps transfer unchanged.
* **Scale cancellation in the cosine epilogue.** Class hypervectors are
  stored as int8 with a per-class scale; because the final score is a
  *cosine*, the class scale cancels against the class norm — the epilogue
  only ever needs the L2 norm of the *quantized* class vector. The only
  approximation the int path introduces is int8 (or ±1) rounding of the
  slabs and class tiles (AUC gap bounded in the benchmark ``--check``).

Accumulator discipline (all bounds checked by
:func:`assert_int_datapath_fits` + hypothesis property tests):

* window sum-of-squares: exact int32 summed-area table of ``codes**2``
  (``<= H*W*(2^bits-1)^2``) — the float SAT would lose exactness past
  2^24;
* fragment projection: every partial sum — matmul entries, rolled
  diagonals, window aggregates — is ``<= h*w*(2^bits-1)*127`` in
  magnitude: int32 with orders of magnitude of headroom at 8-bit codes
  and paper frame/window sizes.

Integer accumulation is associative, so the int path is **bitwise
deterministic across runs** regardless of scheduling — asserted in CI.
(It is also why this rewrite is score-for-score bit-identical to the old
expanded-slab layout: same quantized int8 values, same exact integer sums,
same float epilogue — the golden int8 fixtures did not move.)

Precompute mirrors the float path's mutability split: class-independent
:class:`IntScoreGeometry` (quantized base slabs, rotation gather) vs the
jitted device-side :func:`retile_classes_int` /
:func:`retile_classes_int_fleet` (classifier install = gather + int8
quantize per class), so online adaptation never re-runs the host
precompute mid-stream.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.encoding import NonLin, apply_nonlinearity
from repro.kernels import sliding_scores as _ss

Array = jax.Array

INT32_MAX = 2**31 - 1

#: int8 symmetric quantization range (saturating at +-127 keeps the
#: representation sign-symmetric; -128 is never produced)
_QMAX = 127

#: per-grid-step VMEM working-set budget the int geometry must fit (half a
#: typical 16 MB TPU core VMEM, leaving room for double buffering). The old
#: expanded-slab layout exceeds this at large W; the rolling-shift layout
#: stays under it — see int_datapath_bounds / tests/test_workingset.py.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

#: geometry quantization modes: "int8" (symmetric 8-bit slabs) or "binary"
#: (sign-quantized ±1 slabs and class HVs)
INT_MODES = ("int8", "binary")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class IntScoreGeometry:
    """Class-independent int-kernel precompute (see module docstring).

    ``slabs_q`` is the quantized **base** slab — the same circularly padded
    ``(n_dt, h, slab_width(TD, W))`` layout as the float
    :class:`~repro.kernels.sliding_scores.ScoreGeometry`, int8-quantized
    with the shared ``slab_scale`` (``mode="int8"``) or sign-quantized to
    ±1 with ``slab_scale = mean |slab|`` (``mode="binary"``). Every
    shifted view ``slabs_q[dt, r, i + j]`` the projection needs is built
    *inside* the kernel by rolling — nothing grows with ``W`` beyond the
    ``W`` halo columns, rounded up to a lane multiple.
    """
    slabs_q: Array     # (n_dt, h, slab_width(TD, W)) int8 base slabs
    bias_t: Array      # (n_dt, mx, TD) f32 pre-rotated RFF bias tiles
    idx: Array         # (n_dt, mx, TD) i32 rotation gather into a (D,) vec
    valid: Array       # (n_dt, 1, TD) f32: 1 on real components, 0 on pad
    slab_scale: Array  # () f32: slab ~= slabs_q * slab_scale
    block_d: int = dataclasses.field(metadata={"static": True})
    w: int = dataclasses.field(metadata={"static": True})
    stride: int = dataclasses.field(metadata={"static": True})
    mode: str = dataclasses.field(default="int8", metadata={"static": True})


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class IntScoreTiles:
    """Geometry + quantized class tiles: the int kernel's input bundle.

    ``cpos_t``/``cneg_t`` are ``(n_dt, mx, TD)`` int8 for a shared
    classifier or ``(S, n_dt, mx, TD)`` (with ``(S,)`` norms) per-stream;
    ±1-valued under ``geom.mode == "binary"``. ``c*_norm`` is the L2 norm
    of the *quantized* class vector — the per-class quantization scale
    cancels in the cosine epilogue, so it is never stored.
    """
    geom: IntScoreGeometry
    cpos_t: Array     # ([S,] n_dt, mx, TD) int8 positive class tiles
    cneg_t: Array     # ([S,] n_dt, mx, TD) int8 negative class tiles
    cpos_norm: Array  # ([S]) f32 L2 of the quantized positive class vector
    cneg_norm: Array  # ([S]) f32 L2 of the quantized negative class vector


# ---------------------------------------------------------------------------
# int4 wire format (two 4-bit codes per byte along the row axis)
# ---------------------------------------------------------------------------

def _unpack_nibbles_i32(packed: Array) -> Array:
    """``(..., W/2)`` packed bytes -> ``(..., W)`` int32 4-bit codes.

    The kernel-side twin of :func:`repro.sensing.adc.unpack_nibbles`
    (low nibble first); parity between the two is pinned in
    ``tests/test_adc_quantize.py``.
    """
    p = packed.astype(jnp.int32)
    lo = jnp.bitwise_and(p, 0xF)
    hi = jnp.right_shift(p, 4)
    return jnp.concatenate([lo[..., None], hi[..., None]],
                           axis=-1).reshape(*p.shape[:-1], -1)


# ---------------------------------------------------------------------------
# Bounds: the no-overflow AND fits-VMEM contract of the int datapath
# ---------------------------------------------------------------------------

def int_datapath_bounds(adc_bits: int, H: int, W: int, h: int, w: int,
                        stride: int = 1, block_d: int = 512,
                        packed: bool = False) -> dict:
    """Worst-case int32 accumulators + VMEM working set of the datapath.

    Accumulator magnitudes (exactness contract):

    * ``sumsq`` — the summed-area table of squared codes over a full
      frame (the window-norm pass);
    * ``acc``  — one fragment projection: ``h*w`` products of a max code
      with a max int8 slab entry.

    Both must stay below ``INT32_MAX`` for the path to be exact.

    VMEM working set per grid step (scaling contract — the regression
    guard for the expanded-slab blow-up this layout replaced):

    * ``vmem_bytes`` — the rolling-shift layout: codes block + base slabs
      ``h * slab_width(TD, W)`` + the bounded ``O(W_CHUNK * TD)`` roll
      scratch + bias/class/acc tiles. O(window) in ``W``.
    * ``vmem_expanded_bytes`` — what the old all-``W`` pre-expanded
      ``(h*W, TD)`` slab operand would have needed at the same config:
      linear in ``W``.
    * ``vmem_limit_bytes`` — the :data:`VMEM_BUDGET_BYTES` budget
      ``vmem_bytes`` must not exceed.

    ``stride``/``block_d`` default to the most conservative values
    (``stride=1`` maximizes the window count ``mx``); pass the real ones
    for a tight estimate. ``packed=True`` halves the code-block bytes
    (the int4 wire format).

    ``fits`` is the conjunction: accumulators exact AND working set under
    budget.
    """
    cmax = (1 << adc_bits) - 1
    sumsq = H * W * cmax * cmax
    acc = h * w * cmax * _QMAX

    td = block_d
    mx = max((W - w) // stride + 1, 1)
    wc = min(W, _ss.W_CHUNK)
    codes_bytes = H * (W // 2 if packed else W)           # uint8 wire codes
    slab_bytes = h * _ss.slab_width(td, W)                # int8 base slabs
    scratch_bytes = 3 * wc * (td + wc - 1) * 4            # P + roll + select
    common = (codes_bytes                                 # codes block
              + mx * td * 4 + td * 4                      # f32 bias + valid
              + 2 * mx * td                               # int8 class tiles
              + mx * td * 4)                              # int32 acc
    vmem = common + slab_bytes + scratch_bytes
    vmem_expanded = common + h * W * td                   # old (h*W, TD) slab

    return {"sumsq": sumsq, "acc": acc, "int32_max": INT32_MAX,
            "vmem_bytes": vmem, "vmem_expanded_bytes": vmem_expanded,
            "vmem_limit_bytes": VMEM_BUDGET_BYTES,
            "fits": (max(sumsq, acc) <= INT32_MAX
                     and vmem <= VMEM_BUDGET_BYTES)}


def assert_int_datapath_fits(adc_bits: int, H: int, W: int, h: int,
                             w: int, stride: int = 1, block_d: int = 512,
                             packed: bool = False) -> None:
    """Raise unless the int datapath is exact AND fits the VMEM budget.

    Two distinct failure modes, two distinct errors:

    * int32 accumulator overflow (too many ADC bits for the window size)
      — exactness would silently break;
    * per-grid-step working set over :data:`VMEM_BUDGET_BYTES` — the
      bound the old expanded-slab layout violated at large ``W`` (it
      stored all ``W`` shifts as an ``(h*W, TD)`` operand); the
      rolling-shift layout keeps the live set O(window), so tripping this
      now means a genuinely oversized (window, tile) configuration.
    """
    b = int_datapath_bounds(adc_bits, H, W, h, w, stride=stride,
                            block_d=block_d, packed=packed)
    if max(b["sumsq"], b["acc"]) > INT32_MAX:
        raise ValueError(
            f"int datapath would overflow int32 at adc_bits={adc_bits}, "
            f"frame {H}x{W}, window {h}x{w}: worst-case accumulators "
            f"sumsq={b['sumsq']}, acc={b['acc']} exceed {INT32_MAX}; "
            f"use fewer ADC bits / smaller frames or precision='float32'")
    if b["vmem_bytes"] > b["vmem_limit_bytes"]:
        raise ValueError(
            f"int datapath working set {b['vmem_bytes']} B exceeds the "
            f"{b['vmem_limit_bytes']} B VMEM budget at frame {H}x{W}, "
            f"window {h}x{w}, block_d={block_d}; shrink block_d or the "
            f"frame width")


# ---------------------------------------------------------------------------
# Precompute: geometry (host, per model-geometry) + class tiles (device)
# ---------------------------------------------------------------------------

def _quantize_sym(x: Array, scale: Array) -> Array:
    """Symmetric int8 quantization at a given positive scale."""
    return jnp.clip(jnp.round(x / scale), -_QMAX, _QMAX).astype(jnp.int8)


def precompute_geometry_int(B0: Array, b: Array, *, W: int, w: int,
                            stride: int, block_d: int = 512,
                            mode: str = "int8") -> IntScoreGeometry:
    """Host-side, once per (model-geometry, frame-width).

    Builds on the float :func:`~repro.kernels.sliding_scores.
    precompute_geometry` (same slab/bias/rotation content), then quantizes
    the base slabs *in place* — int8 at the shared max-abs scale
    (``mode="int8"``), or sign-quantized ±1 at ``scale = mean |slab|``
    (``mode="binary"``, the L2-optimal 1-bit scale a la XNOR-Net — it
    keeps the normalized projection on the float path's scale, which the
    RFF nonlinearity is sensitive to). No shift is ever materialized here:
    the kernel rolls them out per grid step. The tile layout (and the
    padded tail's ``valid`` mask) is the float geometry's.
    """
    if mode not in INT_MODES:
        raise ValueError(f"mode must be one of {INT_MODES}, got {mode!r}")
    geom = _ss.precompute_geometry(B0, b, W=W, w=w, stride=stride,
                                   block_d=block_d)
    # the scales weigh the columns a kept lane reads: the slab's lane
    # padding repeats base columns and would shift the binary mean
    read = geom.slabs[..., :geom.block_d + W - 1]
    if mode == "binary":
        scale = jnp.maximum(jnp.mean(jnp.abs(read)), 1e-12)
        slabs_q = jnp.where(geom.slabs >= 0, 1, -1).astype(jnp.int8)
    else:
        scale = jnp.maximum(jnp.max(jnp.abs(read)), 1e-12) / _QMAX
        slabs_q = _quantize_sym(geom.slabs, scale)
    return IntScoreGeometry(slabs_q=slabs_q, bias_t=geom.bias_t,
                            idx=geom.idx, valid=geom.valid,
                            slab_scale=scale.astype(jnp.float32),
                            block_d=geom.block_d, w=w, stride=stride,
                            mode=mode)


def _quantize_class(c: Array, mode: str = "int8") -> tuple[Array, Array]:
    """Per-class quantization: ``(codes (D,) int8, ||codes||_2 f32)``.

    ``mode="int8"``: symmetric int8; ``mode="binary"``: sign-quantized ±1
    (norm ``sqrt(D)``). The scale is *not* returned — it cancels in the
    cosine epilogue either way.
    """
    if mode == "binary":
        q = jnp.where(c >= 0, 1, -1).astype(jnp.int8)
    else:
        scale = jnp.maximum(jnp.max(jnp.abs(c)), 1e-12) / _QMAX
        q = _quantize_sym(c, scale)
    return q, jnp.linalg.norm(q.astype(jnp.float32))


@jax.jit
def retile_classes_int(geom: IntScoreGeometry, class_hvs: Array
                       ) -> IntScoreTiles:
    """Device-side classifier (re-)tiling: ``(2, D)`` -> int8 tiles.

    One gather + quantize per class (int8 or ±1, per ``geom.mode``) — the
    entire cost of installing an updated classifier into the int scoring
    kernel (the online-learning hot path never re-runs
    :func:`precompute_geometry_int`).
    """
    qpos, npos = _quantize_class(class_hvs[1].astype(jnp.float32),
                                 geom.mode)
    qneg, nneg = _quantize_class(class_hvs[0].astype(jnp.float32),
                                 geom.mode)
    return IntScoreTiles(geom=geom, cpos_t=qpos[geom.idx],
                         cneg_t=qneg[geom.idx],
                         cpos_norm=npos, cneg_norm=nneg)


@jax.jit
def retile_classes_int_fleet(geom: IntScoreGeometry, class_hvs: Array
                             ) -> IntScoreTiles:
    """Per-stream classifier tiling: ``(S, 2, D)`` -> stacked int8 tiles."""
    def one(chvs):
        qpos, npos = _quantize_class(chvs[1].astype(jnp.float32), geom.mode)
        qneg, nneg = _quantize_class(chvs[0].astype(jnp.float32), geom.mode)
        return qpos[geom.idx], qneg[geom.idx], npos, nneg

    cpos_t, cneg_t, npos, nneg = jax.vmap(one)(class_hvs)
    return IntScoreTiles(geom=geom, cpos_t=cpos_t, cneg_t=cneg_t,
                         cpos_norm=npos, cneg_norm=nneg)


def precompute_tiles_int(B0: Array, b: Array, class_hvs: Array, *, W: int,
                         w: int, stride: int, block_d: int = 512,
                         mode: str = "int8") -> IntScoreTiles:
    """Host-side all-in-one: geometry + quantized class tiles."""
    geom = precompute_geometry_int(B0, b, W=W, w=w, stride=stride,
                                   block_d=block_d, mode=mode)
    return retile_classes_int(geom, class_hvs)


# ---------------------------------------------------------------------------
# Window norms from raw codes (exact int32 summed-area table)
# ---------------------------------------------------------------------------

def window_sumsq_codes(codes: Array, h: int, w: int, stride: int) -> Array:
    """(my, mx) *exact* int32 sliding-window sums of squared ADC codes."""
    H, W = codes.shape
    my = (H - h) // stride + 1
    mx = (W - w) // stride + 1
    c = codes.astype(jnp.int32)
    sq = jnp.cumsum(jnp.cumsum(c * c, axis=0), axis=1)
    sq = jnp.pad(sq, ((1, 0), (1, 0)))
    ky = jnp.arange(my) * stride
    kx = jnp.arange(mx) * stride
    return (sq[ky[:, None] + h, kx[None, :] + w]
            - sq[ky[:, None] + h, kx[None, :]]
            - sq[ky[:, None], kx[None, :] + w]
            + sq[ky[:, None], kx[None, :]])


def window_norms_codes_batch(codes: Array, h: int, w: int,
                             stride: int) -> Array:
    """(N, my, mx) L2 norms of sliding code windows (float only at sqrt)."""
    ss = jax.vmap(lambda c: window_sumsq_codes(c, h, w, stride))(codes)
    return jnp.sqrt(ss.astype(jnp.float32))


# ---------------------------------------------------------------------------
# The kernel (shared with the float path: repro.kernels.sliding_scores)
# ---------------------------------------------------------------------------

def _check_codes_integer(codes: Array) -> None:
    if not jnp.issubdtype(codes.dtype, jnp.integer):
        raise TypeError(f"int datapath consumes integer ADC codes, got "
                        f"{codes.dtype} — use adc.quantize_codes/pack_codes"
                        f" (or precision='float32')")


@functools.partial(jax.jit, static_argnames=("h", "w", "stride",
                                             "nonlinearity", "interpret",
                                             "frames_per_stream", "packed",
                                             "hyperdim_axes"))
def fragment_scores_batch_int(codes: Array, tiles: IntScoreTiles, *, h: int,
                              w: int, stride: int,
                              nonlinearity: NonLin = "rff",
                              interpret: bool = False,
                              frames_per_stream: int | None = None,
                              packed: bool = False,
                              hyperdim_axes: tuple[str, ...] | None = None
                              ) -> Array:
    """(N, H, W) integer ADC codes -> (N, my, mx) score maps, ONE launch.

    The fused encode->score entry point of the int datapath: raw codes in,
    float score maps out — no float frame is ever materialized, and no
    shifted slab either (rolled out in-kernel, see
    ``sliding_scores._window_acc``). With ``packed=True`` the input is the
    int4 wire format ``(N, H, W/2)`` (two codes per byte, low nibble
    first); nibbles are split inside the kernel, so the HBM->VMEM code
    traffic is halved. The launch is the float path's
    (``sliding_scores.scores_from_tiles``), including the per-stream
    class-tile indexing (``frames_per_stream``) used by adapting fleets.

    Inside a ``shard_map`` that partitions the D-tile axis, pass the mesh
    axis names as ``hyperdim_axes``: each device scores its local slab /
    class-tile shard and the per-tile partials are all_gathered (tiled,
    order-preserving) before the fixed-order fold — bitwise-identical to
    the unsharded launch (see ``sliding_scores._ordered_tile_fold``).
    """
    _check_codes_integer(codes)
    W = codes.shape[-1] * (2 if packed else 1)
    geom = tiles.geom
    assert geom.w == w and geom.stride == stride  # repro-lint: disable=RA001 (static aux fields of the geometry pytree)

    # LSB-free normalization with the slab scale folded in:
    #   s_n = (acc * slab_scale) / ||codes||  =  acc / (||codes|| / scale)
    full = _unpack_nibbles_i32(codes) if packed else codes
    norms = window_norms_codes_batch(full, h, w, stride)      # (N, my, mx)
    norms = jnp.maximum(norms, 1e-8) / geom.slab_scale
    return _ss.scores_from_tiles(
        codes, geom.slabs_q, tiles, norms, h=h, w=w, stride=stride, W=W,
        nonlinearity=nonlinearity, interpret=interpret,
        frames_per_stream=frames_per_stream, packed=packed,
        hyperdim_axes=hyperdim_axes)


# ---------------------------------------------------------------------------
# Pure-jnp twin (the oracle AND the jnp-backend int path)
# ---------------------------------------------------------------------------

def _int_scores_shared(codes, geom: IntScoreGeometry, cpos_t, cneg_t, *,
                       h: int, w: int, stride: int,
                       nonlinearity: NonLin,
                       hyperdim_axes: tuple[str, ...] | None = None):
    """Shared-classifier jnp int path -> ``(dpos, dneg, qq) (N, my, mx)``.

    Same quantized operands and the same int32 accumulation as the kernel
    (the identical ``sliding_scores._window_acc`` core, vmapped); only the
    (float) epilogue can differ by rounding. The classifier dots reduce
    per D-tile first and then fold the tiles in the kernel's fixed
    left-to-right order (``_ordered_tile_fold``) — so this path, too, is
    bitwise-invariant to sharding the tile axis over ``hyperdim_axes``.
    Materializes ``(N, my, mx, D)`` projections — the validation/CPU
    path, not the deployment one.
    """
    N, H, W = codes.shape
    my = (H - h) // stride + 1
    mx = (W - w) // stride + 1
    td = geom.block_d
    ky = jnp.arange(my) * stride
    blocks = codes[:, ky[:, None] + jnp.arange(h)[None, :], :]  # (N,my,h,W)

    # same reuse core as the kernel, with the log-step alignment (the
    # strided rotate has no batching rule), vmapped over (row-band,
    # D-tile) and mapped over frames in small batches: the per-band
    # products are (W_CHUNK, TD + W_CHUNK - 1) int32 each, too many to
    # hold for a whole chunk
    acc = jax.lax.map(jax.vmap(lambda blk: jax.vmap(
        lambda slab: _ss._window_acc(blk, slab, W=W, td=td, w=w,
                                     stride=stride, mx=mx))(geom.slabs_q)),
        blocks, batch_size=8)                      # (N, my, n_dt, mx, TD)
    acc = acc.transpose(0, 1, 3, 2, 4)             # (N, my, mx, n_dt, TD)
    norms = window_norms_codes_batch(codes, h, w, stride)
    norms = jnp.maximum(norms, 1e-8) / geom.slab_scale
    s_n = acc.astype(jnp.float32) / norms[..., None, None]
    bias = geom.bias_t.transpose(1, 0, 2)[None, None]     # (1,1,mx,n_dt,TD)
    valid = geom.valid.transpose(1, 0, 2)[None, None]     # (1,1,1,n_dt,TD)
    phi = apply_nonlinearity(s_n, bias, nonlinearity) * valid
    cpos = cpos_t.transpose(1, 0, 2)[None, None].astype(jnp.float32)
    cneg = cneg_t.transpose(1, 0, 2)[None, None].astype(jnp.float32)
    # per-tile partials (reduce TD only), then the shared fixed-order fold
    fold = lambda x: _ss._ordered_tile_fold(jnp.moveaxis(x, 3, 0),
                                            hyperdim_axes)
    dpos = fold(jnp.sum(phi * cpos, axis=4))       # (N, my, mx)
    dneg = fold(jnp.sum(phi * cneg, axis=4))
    qq = fold(jnp.sum(phi * phi, axis=4))
    return dpos, dneg, qq


@functools.partial(jax.jit, static_argnames=("h", "w", "stride",
                                             "nonlinearity",
                                             "frames_per_stream", "packed",
                                             "hyperdim_axes"))
def fragment_scores_batch_int_ref(codes: Array, tiles: IntScoreTiles, *,
                                  h: int, w: int, stride: int,
                                  nonlinearity: NonLin = "rff",
                                  frames_per_stream: int | None = None,
                                  packed: bool = False,
                                  hyperdim_axes: tuple[str, ...] | None
                                  = None) -> Array:
    """Pure-jnp twin of :func:`fragment_scores_batch_int`.

    Identical quantized operands and int32 accumulation (``packed`` codes
    are unpacked up front — nibble unpacking is value-exact, so the
    accumulation order is untouched); serves as the parity oracle for the
    kernel and as the ``backend="jnp"`` execution of the integer
    precisions in the streaming runtimes.
    """
    _check_codes_integer(codes)
    if packed:
        codes = _unpack_nibbles_i32(codes)
    geom = tiles.geom
    per_stream = tiles.cpos_t.ndim == 4
    if per_stream:
        if frames_per_stream is None:
            raise ValueError("per-stream class tiles need frames_per_stream")
        N, H, W = codes.shape
        S = tiles.cpos_t.shape[0]
        C = frames_per_stream
        if S * C != N:
            raise ValueError(f"per-stream tiles: S={S} streams x "
                             f"C={C} frames != batch N={N}")
        dpos, dneg, qq = jax.vmap(
            lambda cs, cp, cn: _int_scores_shared(
                cs, geom, cp, cn, h=h, w=w, stride=stride,
                nonlinearity=nonlinearity, hyperdim_axes=hyperdim_axes))(
                    codes.reshape(S, C, H, W), tiles.cpos_t, tiles.cneg_t)
        my_mx = dpos.shape[2:]
        dpos, dneg, qq = (x.reshape(N, *my_mx) for x in (dpos, dneg, qq))
    else:
        dpos, dneg, qq = _int_scores_shared(
            codes, geom, tiles.cpos_t, tiles.cneg_t, h=h, w=w,
            stride=stride, nonlinearity=nonlinearity,
            hyperdim_axes=hyperdim_axes)
    return _ss._cosine_epilogue(dpos, dneg, qq, tiles, per_stream,
                                frames_per_stream or 0)
