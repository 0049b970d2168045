"""Public jit'd wrappers for the Pallas kernels.

The kernels run compiled on a TPU and in Pallas interpret mode on the CPU
backend (where the tests run); on any other backend the wrappers raise
rather than hide the device behind the interpreter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.encoding import NonLin
from repro.kernels import hdc_encode as _enc
from repro.kernels import similarity as _sim
from repro.kernels import sliding_scores as _ss
from repro.kernels import sliding_scores_int as _ssi

Array = jax.Array


def _interpret() -> bool:
    """Compiled on TPU, interpreted on CPU; any other backend raises."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels target TPU (and run interpreted "
                       f"on CPU for tests); backend {backend!r} has neither")


def hdc_encode(x: Array, B: Array, b: Array, *,
               nonlinearity: NonLin = "rff", normalize: bool = True,
               block_n: int = 128, block_d: int = 512,
               block_k: int = 512) -> Array:
    """Fused normalize + project + RFF nonlinearity (kernel-backed)."""
    if x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    if normalize:
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-8)
    return _enc.hdc_encode(x, B, b, nonlinearity=nonlinearity,
                           block_n=block_n, block_d=block_d,
                           block_k=block_k, interpret=_interpret())


def similarity(queries: Array, class_hvs: Array, *, block_n: int = 256,
               block_d: int = 1024) -> Array:
    """Fused cosine class scores (kernel-backed)."""
    return _sim.similarity(queries, class_hvs, block_n=block_n,
                           block_d=block_d, interpret=_interpret())


precompute_tiles = _ss.precompute_tiles
precompute_geometry = _ss.precompute_geometry
retile_classes = _ss.retile_classes
retile_classes_fleet = _ss.retile_classes_fleet
ScoreTiles = _ss.ScoreTiles
ScoreGeometry = _ss.ScoreGeometry

# integer datapath twins (repro.kernels.sliding_scores_int): int8, the
# packed-int4 wire format, and the ±1 binary mode all share these
precompute_tiles_int = _ssi.precompute_tiles_int
precompute_geometry_int = _ssi.precompute_geometry_int
retile_classes_int = _ssi.retile_classes_int
retile_classes_int_fleet = _ssi.retile_classes_int_fleet
IntScoreTiles = _ssi.IntScoreTiles
IntScoreGeometry = _ssi.IntScoreGeometry
assert_int_datapath_fits = _ssi.assert_int_datapath_fits
int_datapath_bounds = _ssi.int_datapath_bounds


def fragment_score_map(frame: Array, class_hvs: Array, B0: Array, b: Array,
                       *, h: int, w: int, stride: int,
                       nonlinearity: NonLin = "rff",
                       tiles: _ss.ScoreTiles | None = None,
                       block_d: int = 512) -> Array:
    """Frame -> (my, mx) detection-score map via the reuse kernel.

    For repeated calls, precompute ``tiles`` once with
    :func:`precompute_tiles` and pass it in (the per-model rotation
    precompute is the whole point of the unrolled-orientation trick).
    """
    W = frame.shape[-1]
    if tiles is None:
        tiles = _ss.precompute_tiles(B0, b, class_hvs, W=W, w=w,
                                     stride=stride, block_d=block_d)
    return _ss.fragment_scores(frame, tiles, h=h, w=w, stride=stride,
                               nonlinearity=nonlinearity,
                               interpret=_interpret())


def fragment_score_map_batch(frames: Array, class_hvs: Array, B0: Array,
                             b: Array, *, h: int, w: int, stride: int,
                             nonlinearity: NonLin = "rff",
                             tiles: _ss.ScoreTiles | None = None,
                             block_d: int = 512,
                             hyperdim_axes: tuple[str, ...] | None = None
                             ) -> Array:
    """(N, H, W) frames -> (N, my, mx) score maps in ONE kernel launch.

    The streaming hot path: every frame in the chunk reuses the same
    :class:`ScoreTiles` precompute. Pass ``tiles`` explicitly when scoring
    many chunks with one model so the precompute is paid once.
    """
    W = frames.shape[-1]
    if tiles is None:
        tiles = _ss.precompute_tiles(B0, b, class_hvs, W=W, w=w,
                                     stride=stride, block_d=block_d)
    return _ss.fragment_scores_batch(frames, tiles, h=h, w=w, stride=stride,
                                     nonlinearity=nonlinearity,
                                     interpret=_interpret(),
                                     hyperdim_axes=hyperdim_axes)


def fragment_score_map_batch_int(codes: Array, class_hvs: Array, B0: Array,
                                 b: Array, *, h: int, w: int, stride: int,
                                 nonlinearity: NonLin = "rff",
                                 tiles: _ssi.IntScoreTiles | None = None,
                                 block_d: int = 512,
                                 packed: bool = False,
                                 mode: str = "int8",
                                 hyperdim_axes: tuple[str, ...] | None = None
                                 ) -> Array:
    """(N, H, W) integer ADC codes -> (N, my, mx) score maps, ONE launch.

    The integer datapath's streaming hot path: raw codes flow into the
    fused encode->score kernel untouched (int32 accumulation, shifted
    slabs rolled out in-kernel, float only at the similarity epilogue).
    ``packed=True`` consumes the int4 wire format (``(N, H, W/2)`` bytes,
    two codes each); ``mode`` selects the slab/class quantization
    ("int8" or "binary") when ``tiles`` is built here. Pass ``tiles``
    from :func:`precompute_tiles_int` to amortize the quantized
    precompute across chunks.
    """
    W = codes.shape[-1] * (2 if packed else 1)
    if tiles is None:
        tiles = _ssi.precompute_tiles_int(B0, b, class_hvs, W=W, w=w,
                                          stride=stride, block_d=block_d,
                                          mode=mode)
    return _ssi.fragment_scores_batch_int(codes, tiles, h=h, w=w,
                                          stride=stride,
                                          nonlinearity=nonlinearity,
                                          interpret=_interpret(),
                                          packed=packed,
                                          hyperdim_axes=hyperdim_axes)


def fragment_score_map_fleet_int(codes: Array, class_hvs: Array, B0: Array,
                                 b: Array, *, h: int, w: int, stride: int,
                                 nonlinearity: NonLin = "rff",
                                 tiles: _ssi.IntScoreTiles | None = None,
                                 block_d: int = 512,
                                 packed: bool = False,
                                 mode: str = "int8",
                                 hyperdim_axes: tuple[str, ...] | None = None
                                 ) -> Array:
    """(S, C, H, W) code super-chunk -> (S, C, my, mx), ONE launch.

    Int twin of :func:`fragment_score_map_fleet`: per-stream int8 (or ±1)
    class tiles (``tiles.cpos_t.ndim == 4``) ride the stream-indexed
    BlockSpecs of the shared grid; ``packed`` marks int4 wire codes.
    """
    S, C, H, W = codes.shape
    if tiles is not None and tiles.cpos_t.ndim == 4:
        maps = _ssi.fragment_scores_batch_int(
            codes.reshape(S * C, H, W), tiles, h=h, w=w, stride=stride,
            nonlinearity=nonlinearity, interpret=_interpret(),
            frames_per_stream=C, packed=packed,
            hyperdim_axes=hyperdim_axes)
    else:
        maps = fragment_score_map_batch_int(
            codes.reshape(S * C, H, W), class_hvs, B0, b, h=h, w=w,
            stride=stride, nonlinearity=nonlinearity, tiles=tiles,
            block_d=block_d, packed=packed, mode=mode,
            hyperdim_axes=hyperdim_axes)
    return maps.reshape(S, C, *maps.shape[1:])


def fragment_score_map_fleet(frames: Array, class_hvs: Array, B0: Array,
                             b: Array, *, h: int, w: int, stride: int,
                             nonlinearity: NonLin = "rff",
                             tiles: _ss.ScoreTiles | None = None,
                             block_d: int = 512,
                             hyperdim_axes: tuple[str, ...] | None = None
                             ) -> Array:
    """(S, C, H, W) super-chunk -> (S, C, my, mx) score maps, ONE launch.

    The fleet hot path: S concurrent sensor streams contribute C frames
    each; the ``S*C`` axis is flattened into the batch grid of
    :func:`fragment_score_map_batch`, so the whole fleet super-chunk is a
    single ``pallas_call`` against one shared :class:`ScoreTiles`
    precompute. The grid's batch axis is parallel, so per-frame numerics
    are identical to S independent per-stream calls.
    """
    S, C, H, W = frames.shape
    if tiles is not None and tiles.cpos_t.ndim == 4:
        # per-stream classifiers (online fleet adaptation): one launch,
        # stream-indexed class-tile BlockSpecs inside the shared grid.
        maps = _ss.fragment_scores_batch(
            frames.reshape(S * C, H, W), tiles, h=h, w=w, stride=stride,
            nonlinearity=nonlinearity, interpret=_interpret(),
            frames_per_stream=C, hyperdim_axes=hyperdim_axes)
    else:
        maps = fragment_score_map_batch(
            frames.reshape(S * C, H, W), class_hvs, B0, b, h=h, w=w,
            stride=stride, nonlinearity=nonlinearity, tiles=tiles,
            block_d=block_d, hyperdim_axes=hyperdim_axes)
    return maps.reshape(S, C, *maps.shape[1:])
