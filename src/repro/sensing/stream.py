"""Batched streaming runtime: chunked scoring + gating + online learning.

The paper's sensing loop (§III-B/C) scores *every* incoming frame with the
HDC HyperSense model and gates the expensive high-precision path in real
time. ``repro.core.sensor_control.simulate_stream`` does that one frame per
call — one kernel launch (or one jnp dispatch) per frame. This module is
the throughput path: frames are consumed in fixed-size chunks and each
chunk runs

  batched fragment scoring  ->  frame_detection_score  ->  threshold
  ->  SensorController hysteresis (as a ``lax.scan``)
  ->  (optionally) an online classifier update

inside a single jitted step. On the ``pallas`` backend the whole chunk is
ONE kernel launch (grid ``(N, n_dt)``).

**Mutable model state.** The model is no longer frozen at construction:
every chunk threads a :class:`StreamState` pytree — class hypervectors,
per-stream gate holds, absolute frame index — through
:func:`super_chunk_fn`. With ``adapt=None`` the class hypervectors simply
pass through unchanged and the step is the frozen scorer (bitwise
identical to the pre-online-learning runtime on the ``pallas`` backend).
With an :class:`~repro.core.online.AdaptConfig` the step also

  1. extracts each frame's *top-scoring fragment*, re-encodes it (an
     ``O(h*w*D)`` matmul per frame — tiny next to scoring), and
  2. folds those sample hypervectors through the similarity-scaled
     perceptron rule (``repro.core.online``) — supervised label feedback
     or confidence-gated pseudo-labels — producing the next chunk's
     classifier.

On the ``pallas`` backend the adaptive step holds only the class-agnostic
:class:`~repro.kernels.sliding_scores.ScoreGeometry`; the fresh classifier
is installed by the jitted, device-side ``retile_classes`` (one gather per
class) — no host-side ``precompute_tiles`` ever runs mid-stream.

Within a chunk, scoring uses the chunk-start classifier while the update
folds the chunk's samples sequentially (exactly ``retrain_epoch`` over the
extracted sample sequence); ``chunk_size=1`` recovers pure per-frame
online learning.

:func:`gate_scan` is the exact jnp twin of
:class:`~repro.core.sensor_control.SensorController`; the carried ``hold``
state crosses chunk boundaries, so chunking is invisible:
:func:`simulate_stream_batched` returns :class:`StreamStats` identical to
the frame-at-a-time ``simulate_stream``.

**Closed capture loop.** With ``control=``
(:class:`~repro.core.sensor_control.CaptureConfig`) the gate drives the
ADC itself: :func:`control_scan` (the jnp twin of
:class:`~repro.core.sensor_control.RateController`) carries a per-stream
``(hold, phase)`` state so the decision at frame ``t`` decides whether
frame ``t+1`` is converted at all — idle trickle at ``base_rate_hz`` /
``adc_bits``, gated bursts at ``active_rate_hz`` with high-precision
frames gathered into a bounded buffer (:func:`hp_capture`,
``runner.drain_hp()``). Every runner keeps a
:class:`~repro.core.sensor_control.CaptureLog`;
:func:`repro.core.energy.from_capture_log` bills from it directly.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hypersense, online
from repro.core.encoding import encode_fragments, flat_perm_base
from repro.core.hypersense import HyperSenseModel, frame_detection_score
from repro.core.online import AdaptConfig
from repro.core.sensor_control import (CaptureConfig, CaptureLog,
                                       ControllerConfig, StreamStats,
                                       assemble_capture_log, decimation,
                                       stats_from)
from repro.sensing import adc as adc_sim

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StreamState:
    """Mutable stream state threaded through every chunk step.

    ``class_hvs`` is ``(2, D)`` for a single stream / fleet-shared
    classifier, or ``(S, 2, D)`` when a fleet adapts per-stream models.
    ``holds`` is the ``(S,)`` controller hysteresis state; ``phases`` the
    ``(S,)`` closed-loop ADC state (frames until the next idle
    low-precision sample — identically zero in open-loop mode);
    ``frame_idx`` the absolute index of the next frame (i32 scalar).
    """
    class_hvs: Array
    holds: Array
    phases: Array
    frame_idx: Array


def init_stream_state(class_hvs: Array, n_streams: int,
                      per_stream: bool = False) -> StreamState:
    """Fresh state: model's classifier, zero holds/phases, frame 0."""
    chvs = jnp.asarray(class_hvs)
    if per_stream and chvs.ndim == 2:
        chvs = jnp.broadcast_to(chvs, (n_streams, *chvs.shape))
    return StreamState(class_hvs=chvs,
                       holds=jnp.zeros((n_streams,), jnp.int32),
                       phases=jnp.zeros((n_streams,), jnp.int32),
                       frame_idx=jnp.zeros((), jnp.int32))


def validate_runner_args(chunk_size: int, adc_bits: int | None,
                         adc_sigma: float, precision: str) -> None:
    """Shared constructor validation for every streaming front-end.

    ``StreamRunner``, :class:`~repro.sensing.fleet.FleetRunner` and
    :class:`~repro.launch.serve.FleetService` all accept the same
    (chunk, ADC, precision) surface; this is the ONE place its
    consistency rules live, so the three cannot drift apart.
    """
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    if adc_sigma > 0.0 and adc_bits is None:
        raise ValueError("adc_sigma > 0 without adc_bits: the ADC is "
                         "only in the loop when adc_bits is set")
    if precision not in adc_sim.PRECISIONS:
        raise ValueError(f"precision must be one of "
                         f"{adc_sim.PRECISIONS}, got {precision!r}")
    if precision in adc_sim.INT_PRECISIONS and adc_bits is None:
        raise ValueError(f'precision="{precision}" consumes ADC codes: '
                         "set adc_bits (the simulated converter's depth)")
    if precision == "int4" and adc_bits is not None and adc_bits > 4:
        raise ValueError(f'precision="int4" packs two codes per byte, '
                         f"so adc_bits must be <= 4 (got {adc_bits})")


def adc_view(frames: Array, bits: int, *, sigma: float = 0.0,
             key: Array | None = None, start_index: int = 0) -> Array:
    """Low-precision ADC capture of ``(N, H, W)`` frames (paper Fig. 3).

    Thermal noise (``sigma > 0``) is keyed by *absolute frame index*
    (``start_index + i``), not by call count — re-slicing a stream into
    different ``process()`` calls yields bit-identical captures, which is
    what keeps the runners' slicing-invariance property intact with the
    ADC in the loop.
    """
    return adc_sim.quantize(
        _noisy_capture(frames, sigma, key, start_index), bits)


def _noisy_capture(frames: Array, sigma: float, key: Array | None,
                   start_index: int) -> Array:
    """Pre-conversion thermal noise, keyed by absolute frame index.

    The ONE implementation both ADC views share — the float and codes
    captures are the same converter by construction, so their noise
    keying can never drift apart.
    """
    frames = jnp.asarray(frames)
    if sigma <= 0.0:
        return frames
    if key is None:
        raise ValueError("adc noise (sigma > 0) requires a PRNG key")
    idx = jnp.arange(frames.shape[0]) + start_index
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
    return jax.vmap(
        lambda k, f: adc_sim.adc_noise(k, f, sigma))(keys, frames)


def adc_view_codes(frames: Array, bits: int, *, sigma: float = 0.0,
                   key: Array | None = None, start_index: int = 0) -> Array:
    """Raw integer ADC codes of ``(N, H, W)`` frames (the int datapath).

    The codes twin of :func:`adc_view` — same capture (identical noise
    keying by absolute frame index, identical quantizer), but the output
    is the packed integer codes the fused int kernel consumes directly,
    never the float reconstruction. Integer input is treated as
    already-converted codes and only (re)packed — feeding a code stream
    back through is the identity, mirroring ``quantize`` idempotence.
    Codes outside ``[0, 2^bits - 1]`` are rejected (when the values are
    concrete) rather than silently wrapped by the pack.
    """
    frames = jnp.asarray(frames)
    if jnp.issubdtype(frames.dtype, jnp.integer):
        if sigma > 0.0:
            raise ValueError("adc noise applies before conversion; input "
                             "is already integer ADC codes")
        adc_sim.check_codes_range(frames, bits)
        return adc_sim.pack_codes(frames.astype(jnp.int32), bits)
    frames = _noisy_capture(frames, sigma, key, start_index)
    return adc_sim.pack_codes(adc_sim.quantize_codes(frames, bits), bits)


def gate_scan(decisions: Array, hold_frames: int,
              init_hold: Array | int = 0) -> tuple[Array, Array]:
    """Jittable ``SensorController``: ``(gated (N,) bool, holds (N,) i32)``.

    ``holds[i]`` is the controller state *after* frame ``i`` — feed
    ``holds[last_real_frame]`` back as ``init_hold`` of the next chunk.
    """
    def step(hold, fired):
        gated = fired | (hold > 0)
        hold = jnp.where(fired, hold_frames, jnp.maximum(hold - 1, 0))
        return hold, (gated, hold)

    _, (gated, holds) = jax.lax.scan(
        step, jnp.asarray(init_hold, jnp.int32), decisions.astype(bool))
    return gated, holds


def control_scan(decisions: Array, hold_frames: int, decim: int,
                 init_hold: Array | int = 0, init_phase: Array | int = 0
                 ) -> tuple[Array, Array, Array, Array]:
    """Jittable :class:`~repro.core.sensor_control.RateController`:
    ``(sampled, gated, holds, phases)``, each ``(N,)``.

    The closed-loop twin of :func:`gate_scan`: the carried ``(hold,
    phase)`` pair decides per frame whether the LP ADC converts it at
    all — a skipped frame's decision input is masked out (the HDC never
    saw it), which is how the gate decision at frame ``t`` modulates
    capture at ``t+1`` *inside* one scan. ``holds[i]``/``phases[i]`` are
    the state after frame ``i``; feed the last valid frame's values back
    as the next chunk's ``init_*``. With ``decim == 1`` the phase is
    identically 0, every frame is sampled, and ``gated``/``holds`` are
    bitwise :func:`gate_scan`'s.
    """
    def step(carry, f):
        hold, phase = carry
        sampled = (phase == 0) | (hold > 0)
        fired = f & sampled
        gated = fired | (hold > 0)
        hold = jnp.where(fired, hold_frames, jnp.maximum(hold - 1, 0))
        phase = jnp.where(sampled, decim - 1, phase - 1)
        return (hold, phase), (sampled, gated, hold, phase)

    init = (jnp.asarray(init_hold, jnp.int32),
            jnp.asarray(init_phase, jnp.int32))
    _, (sampled, gated, holds, phases) = jax.lax.scan(
        step, init, decisions.astype(bool))
    return sampled, gated, holds, phases


@functools.partial(jax.jit, static_argnames=("k", "bits"))
def hp_capture(raw: Array, gated: Array, n_valid: Array, k: int, bits: int
               ) -> tuple[Array, Array, Array]:
    """Bounded gather buffer: the first ``k`` gated frames of a chunk,
    captured at the high-precision depth — the closed loop's deliverable.

    ``raw`` is the ``(C, H, W)`` *raw* (pre-LP-conversion) chunk; returns
    ``(buf (k, H, W) float32, idx (k,) i32, count i32)`` where ``idx[j]``
    is the in-chunk frame index materialized in slot ``j`` (``-1`` =
    empty slot) and ``count`` is the total gated frames — ``count > k``
    means the buffer overflowed and ``count - k`` burst frames were
    dropped (the runners surface this as ``hp_dropped``). Fixed shapes
    keep the step a single jit trace for every gate outcome.
    """
    C = raw.shape[0]
    pos = jnp.arange(C)
    take = gated.astype(bool) & (pos < n_valid)
    rank = jnp.cumsum(take) - 1                    # 0-based among taken
    slot = jnp.where(take & (rank < k), rank, k)   # k = spill slot
    q = adc_sim.quantize_per_frame(raw, jnp.where(take, bits, 0))
    buf = jnp.zeros((k + 1, *raw.shape[1:]), jnp.float32).at[slot].set(q)
    idx = jnp.full((k + 1,), -1, jnp.int32).at[slot].set(pos)
    return buf[:k], idx[:k], take.sum()


def resolve_hp_buffer(control: CaptureConfig | None, chunk_size: int,
                      frames_dtype) -> int:
    """Per-chunk HP buffer size for a runner (0 = no materialization).

    The ONE place both runners resolve ``CaptureConfig.hp_buffer``
    (``None`` → ``chunk_size``) and reject integer-code input, which has
    no raw frames to HP-capture from.
    """
    if control is None:
        return 0
    k = chunk_size if control.hp_buffer is None else control.hp_buffer
    if k > 0 and jnp.issubdtype(frames_dtype, jnp.integer):
        raise ValueError(
            "high-precision materialization needs the raw frames; the "
            "input is already low-precision ADC codes — pass "
            "control=CaptureConfig(hp_buffer=0) to run the closed loop "
            "log-only")
    return k


def collect_hp(raw_chunk: Array, gated: Array, n_valid: int, k: int,
               bits: int, base) -> tuple[list[list], int]:
    """Drain one chunk's bounded HP buffers to host land.

    ``raw_chunk`` is ``(S, C, H, W)`` (padded to the chunk size), ``gated``
    the step's ``(S, C)`` gate output. ``base`` offsets the in-chunk frame
    positions to absolute stream indices — a scalar when every stream sits
    at the same absolute frame (the runners), or an ``(S,)`` vector when
    streams run out of phase (the serving layer's ragged slots). Returns
    (one ``[(absolute_frame_idx, hp_frame), ...]`` list per stream — in
    frame order — and the number of burst frames dropped to full
    buffers); shared by every front-end so the drop accounting can never
    diverge.
    """
    buf, idx, cnt = jax.vmap(
        lambda r, gt: hp_capture(r, gt, jnp.int32(n_valid), k, bits))(
            raw_chunk, gated)
    idx, buf = np.asarray(idx), np.asarray(buf)
    base = np.broadcast_to(np.asarray(base, np.int64), (idx.shape[0],))
    out, dropped = [], 0
    for si in range(idx.shape[0]):
        kept = idx[si] >= 0
        out.append(list(zip((base[si] + idx[si][kept]).tolist(),
                            buf[si][kept])))
        dropped += max(int(cnt[si]) - int(kept.sum()), 0)
    return out, dropped


def hp_drain_arrays(entries, frame_hw: tuple[int, int] | None
                    ) -> tuple[np.ndarray, np.ndarray]:
    """One stream's ``[(abs_idx, frame), ...]`` buffer → drain arrays.

    The drain-shape contract every front-end shares: ``(indices (M,)
    int64, frames (M, H, W) float32)`` — an EMPTY drain still carries
    the real frame shape ``(0, H, W)`` (float32 is the ``hp_bits``
    dtype: :func:`hp_capture` materializes bursts as float32
    reconstructions at ``control.hp_bits``), so a consumer can
    ``np.concatenate`` drains across ticks unconditionally — exactly
    what the gated cascade does. Only before any frame has fixed the
    shape (``frame_hw=None``) is the degenerate ``(0, 0, 0)`` returned.
    """
    idx = np.asarray([i for i, _ in entries], np.int64)
    if entries:
        frames = np.stack([np.asarray(f, np.float32) for _, f in entries])
    else:
        hw = (0, 0) if frame_hw is None else tuple(frame_hw)
        frames = np.zeros((0, *hw), np.float32)
    return idx, frames


def _top_fragment_hvs(frames: Array, maps: Array, B0: Array, b: Array, *,
                      h: int, w: int, stride: int, mx: int,
                      nonlinearity) -> Array:
    """Re-encode each frame's top-scoring fragment -> ``(S, C, D)``.

    The online update's sample stream: per frame, the fragment the model
    found most object-like (hard positive on object frames, hard negative
    on empty ones). One ``(h*w, D)`` projection per frame — negligible
    next to the full score map.
    """
    S, C, H, W = frames.shape
    top = jnp.argmax(maps.reshape(S, C, -1), axis=-1)            # (S, C)
    iy = (top // mx) * stride
    ix = (top % mx) * stride
    crop = jax.vmap(jax.vmap(
        lambda f, y, x: jax.lax.dynamic_slice(f, (y, x), (h, w))))
    frags = crop(frames, iy, ix)                                 # (S,C,h,w)
    Bf = flat_perm_base(B0, w)                                   # (h*w, D)
    hv = encode_fragments(frags.reshape(S * C, h, w), Bf, b,
                          nonlinearity=nonlinearity, normalize=True)
    return hv.reshape(S, C, -1)


def super_chunk_fn(frames, state: StreamState, B0, b, tiles, t_score,
                   n_valid, labels, slot_mask=None, *, h, w, stride,
                   nonlinearity, t_detection, hold_frames, backend,
                   adapt: AdaptConfig | None = None,
                   precision: str = "float32", adc_lsb: float = 1.0,
                   decim: int | None = None,
                   park_masked: bool = False,
                   sensor_axes: tuple[str, ...] | None = None,
                   hyperdim_axes: tuple[str, ...] | None = None):
    """One streaming step over an ``(S, C, H, W)`` super-chunk.

    The shared core of both runners: ``StreamRunner`` calls it with
    ``S = 1``, :class:`~repro.sensing.fleet.FleetRunner` with S concurrent
    streams. The ``S*C`` axis is flattened into the batched scorer (one
    kernel launch on the ``pallas`` backend) and each stream's gate is a
    ``vmap``'d :func:`gate_scan`.

    ``state`` carries the mutable model: scoring uses
    ``state.class_hvs``; with ``adapt`` set, the returned state holds the
    chunk-updated classifier. On the ``pallas`` backend ``tiles`` is the
    full host-precomputed :class:`~repro.kernels.sliding_scores.ScoreTiles`
    when frozen (``adapt=None`` — that path's kernel inputs, and hence
    outputs, are bitwise identical to the pre-refactor runtime), or just
    the :class:`~repro.kernels.sliding_scores.ScoreGeometry` when
    adapting — the current classifier is re-tiled *inside* the step by
    the jitted ``retile_classes`` gather.

    ``n_valid`` masks a padded tail chunk; pad frames never fire, never
    contribute updates, and the carried ``(S,)`` hold state is read at the
    last *valid* frame. ``labels`` is ``(S, C)`` i32 — only consumed in
    ``adapt.mode == "label"`` (pass zeros otherwise).

    With an integer precision (``"int8"``, ``"int4"``, ``"binary"``) the
    ``frames`` argument is the *integer ADC code* super-chunk (from
    :func:`adc_view_codes`) and ``tiles`` the int precompute
    (:class:`~repro.kernels.sliding_scores_int.IntScoreTiles`, or the int
    geometry when adapting) — on BOTH backends: the jnp execution of the
    int path is the quantized-operand oracle
    ``fragment_scores_batch_int_ref``, so jnp==pallas parity holds per
    precision. ``"int4"`` codes are nibble-packed here at the kernel
    boundary (two per byte, unpacked in-kernel) — everything outside the
    scorer, including the adapt re-encode, sees plain codes. ``adc_lsb``
    (static; ``v_max/levels`` of the converter) only matters to the
    online-learning re-encode, which dequantizes the top fragment crop —
    scoring itself is LSB-free.

    ``decim`` switches on the *closed capture loop*: ``None`` (default)
    is the open-loop step — every valid frame is LP-converted and the
    gate is the plain :func:`gate_scan` hysteresis, a code path bitwise
    identical to the pre-closed-loop runtime. An integer ``decim`` runs
    :func:`control_scan` instead, with the per-stream ``state.phases``
    ADC state carried across chunks: idle frames are subsampled to one
    LP conversion per ``decim`` frames, a skipped frame can never fire
    (its score is still computed — simulation artifact — but masked out
    of the decision, the gate, and the online update), and ``decim == 1``
    reproduces the open-loop outputs bitwise.

    ``slot_mask`` (``(S,)`` bool, default all-true) marks *real* sensor
    slots: the fleet pads S up to the mesh extent with masked slots so a
    non-divisible fleet still shards (never a recompile or an unsharded
    fallback). Masked slots never fire, never sample, and never
    contribute to a shared-scope update — their presence is an exact
    no-op on every real slot's outputs and on the shared classifier.

    ``park_masked`` additionally freezes the masked slots' *carried
    state* in place: their hold/phase counters (which would otherwise
    decay through the chunk) and, in per-stream scope, their classifier
    rows pass through unchanged. This is the serving layer's slot-pool
    semantics (:class:`repro.launch.serve.FleetService`): a sensor that
    sent no frames this tick experienced no time, so a later reattach
    resumes exactly where it detached. With an all-true ``slot_mask``
    the selects are identities — the parked step is bitwise the plain
    one, which is what lets the service share this trace.

    ``sensor_axes`` / ``hyperdim_axes`` name the mesh axes this step is
    ``shard_map``'d over (None outside a mesh). ``hyperdim_axes`` flows
    to the scorer's tile fold (tiled all_gather before a fixed-order
    reduction — see ``sliding_scores._ordered_tile_fold``);
    ``sensor_axes`` makes the shared-scope online fold all_gather the
    per-shard samples so every device folds the full fleet's samples in
    the identical global time-then-stream order. Both keep outputs
    bitwise-identical to the unsharded step — a ``psum`` of per-shard
    deltas could NOT, because each perceptron step depends on the
    running classifier state.

    Returns ``(scores (S, C), fired, gated, sampled, new_state)``;
    ``sampled`` marks the frames the LP ADC actually converted.
    """
    S, C, H, W = frames.shape
    my = (H - h) // stride + 1
    mx = (W - w) // stride + 1
    class_hvs = state.class_hvs
    per_stream = adapt is not None and adapt.scope == "per-stream"

    if precision in adc_sim.INT_PRECISIONS:
        from repro.kernels import ops as kops
        from repro.kernels import sliding_scores_int as ssi
        if adapt is None:
            ktiles = tiles                       # frozen: IntScoreTiles
        elif per_stream:                         # tiles: IntScoreGeometry
            ktiles = kops.retile_classes_int_fleet(tiles, class_hvs)
        else:
            ktiles = kops.retile_classes_int(tiles, class_hvs)
        packed = precision == "int4"
        kframes = adc_sim.pack_nibbles(frames) if packed else frames
        if backend == "pallas":
            maps = kops.fragment_score_map_fleet_int(
                kframes, class_hvs, B0, b, h=h, w=w, stride=stride,
                nonlinearity=nonlinearity, tiles=ktiles, packed=packed,
                hyperdim_axes=hyperdim_axes)                 # (S,C,my,mx)
        else:
            fps = C if ktiles.cpos_t.ndim == 4 else None
            maps = ssi.fragment_scores_batch_int_ref(
                kframes.reshape(S * C, H, kframes.shape[-1]), ktiles,
                h=h, w=w, stride=stride, nonlinearity=nonlinearity,
                frames_per_stream=fps, packed=packed,
                hyperdim_axes=hyperdim_axes).reshape(S, C, my, mx)
    elif backend == "pallas":
        from repro.kernels import ops as kops
        if adapt is None:
            ktiles = tiles                       # frozen: host precompute
        elif per_stream:                         # tiles is a ScoreGeometry
            ktiles = kops.retile_classes_fleet(tiles, class_hvs)
        else:
            ktiles = kops.retile_classes(tiles, class_hvs)
        maps = kops.fragment_score_map_fleet(
            frames, class_hvs, B0, b, h=h, w=w, stride=stride,
            nonlinearity=nonlinearity, tiles=ktiles,
            hyperdim_axes=hyperdim_axes)                     # (S, C, my, mx)
    elif per_stream:
        maps = jax.vmap(lambda fs, cv: jax.vmap(
            lambda f: hypersense.fragment_score_map(
                f, cv, B0, b, h=h, w=w, stride=stride,
                nonlinearity=nonlinearity, reuse=False,
                backend=backend))(fs))(frames, class_hvs)
    else:
        # the plain reference: crop every fragment and encode it against
        # the materialized base (one matmul) — the reuse formulation
        # materializes (H, W, D) per base row, which no device holds at
        # the paper's frame size
        maps = jax.vmap(lambda f: hypersense.fragment_score_map(
            f, class_hvs, B0, b, h=h, w=w, stride=stride,
            nonlinearity=nonlinearity, reuse=False, backend=backend))(
                frames.reshape(S * C, H, W)).reshape(S, C, my, mx)

    scores = jax.vmap(jax.vmap(
        lambda m: frame_detection_score(m, t_detection)))(maps)  # (S, C)

    # count(s_i > t) > T  <=>  (T+1)-th largest > t, provided T < my*mx;
    # with T >= my*mx the count can never exceed T -> never fires.
    valid = jnp.arange(C) < n_valid
    if t_detection >= my * mx:
        fired = jnp.zeros((S, C), bool)
    else:
        fired = (scores > t_score) & valid[None, :]
    if slot_mask is not None:
        fired = fired & slot_mask[:, None]

    if decim is None:
        sampled = jnp.broadcast_to(valid[None, :], (S, C))
        if slot_mask is not None:
            sampled = sampled & slot_mask[:, None]
        gated, holds_seq = jax.vmap(
            lambda f, h0: gate_scan(f, hold_frames, h0))(fired, state.holds)
        phase_out = state.phases
    else:
        sampled, gated, holds_seq, phases_seq = jax.vmap(
            lambda f, h0, p0: control_scan(f, hold_frames, decim, h0, p0))(
                fired, state.holds, state.phases)
        fired = fired & sampled
        if slot_mask is not None:
            sampled = sampled & slot_mask[:, None]
        phase_out = jnp.where(n_valid > 0,
                              phases_seq[:, jnp.maximum(n_valid - 1, 0)],
                              state.phases)
    hold_out = jnp.where(n_valid > 0,
                         holds_seq[:, jnp.maximum(n_valid - 1, 0)],
                         state.holds)

    if adapt is not None:
        # the int path re-encodes from the dequantized crop (h*w values per
        # frame — never a full float frame); the fragment normalization
        # makes the LSB cancel, so this matches the float path's samples
        # up to int8 rounding of the codes themselves
        obs = (frames.astype(jnp.float32) * jnp.float32(adc_lsb)
               if precision in adc_sim.INT_PRECISIONS else frames)
        hv = _top_fragment_hvs(obs, maps, B0, b, h=h, w=w,
                               stride=stride, mx=mx,
                               nonlinearity=nonlinearity)    # (S, C, D)
        labels = labels.astype(jnp.int32)

        def _shared_fold(chvs, hv, labels, mask2d):
            # One shared classifier: fold samples in time order (stream
            # index breaks ties), matching real arrival order. Under
            # sensor sharding, all_gather the per-shard samples first
            # (tiled = global stream order restored) and run the SAME
            # sequential fold replicated on every device — the perceptron
            # step depends on the running classifier, so this, not a psum
            # of deltas, is the all-reduce that matches unsharded bitwise.
            if sensor_axes:
                hv = jax.lax.all_gather(hv, sensor_axes, axis=0, tiled=True)
                labels = jax.lax.all_gather(labels, sensor_axes, axis=0,
                                            tiled=True)
                mask2d = jax.lax.all_gather(mask2d, sensor_axes, axis=0,
                                            tiled=True)
            s_all, dim = hv.shape[0], hv.shape[-1]
            hv_t = hv.transpose(1, 0, 2).reshape(C * s_all, dim)
            lab_t = labels.T.reshape(C * s_all)
            val_t = mask2d.T.reshape(C * s_all)
            return online.apply_chunk(adapt, chvs, hv_t, lab_t, val_t)[0]

        def _per_stream_fold(chvs, hv, labels, mask2d):
            # lax.map, NOT vmap: XLA's batched dot inside apply_chunk
            # reassociates with the batch extent, so a vmap'd fold is not
            # bitwise stable when sensor sharding changes the per-device
            # batch. lax.map runs each stream through the identical
            # unbatched program — any partition of the stream axis gives
            # the same per-row bits (tests/test_parity_matrix.py pins the
            # full mesh matrix on this).
            return jax.lax.map(
                lambda a: online.apply_chunk(adapt, a[0], a[1],
                                             a[2], a[3])[0],
                (chvs, hv, labels, mask2d))

        if decim is None:
            # masked pad slots contribute nothing (exact no-op selects)
            mask2d = jnp.broadcast_to(valid[None, :], (S, C))
            if slot_mask is not None:
                mask2d = mask2d & slot_mask[:, None]
            if per_stream:
                class_hvs = _per_stream_fold(class_hvs, hv, labels, mask2d)
            else:
                class_hvs = _shared_fold(class_hvs, hv, labels, mask2d)
        else:
            # closed loop: a frame the LP ADC skipped was never scored —
            # it must not feed the online update either (sampled already
            # carries the slot mask)
            seen = sampled & valid[None, :]                     # (S, C)
            if per_stream:
                class_hvs = _per_stream_fold(class_hvs, hv, labels, seen)
            else:
                class_hvs = _shared_fold(class_hvs, hv, labels, seen)

    if park_masked and slot_mask is not None:
        # slot-pool semantics: a masked slot's carried state is parked in
        # place — no hold/phase decay, no classifier churn — so detached
        # or silent sensors resume bitwise where they stopped
        hold_out = jnp.where(slot_mask, hold_out, state.holds)
        phase_out = jnp.where(slot_mask, phase_out, state.phases)
        if class_hvs.ndim == 3:
            class_hvs = jnp.where(slot_mask[:, None, None], class_hvs,
                                  state.class_hvs)

    new_state = StreamState(class_hvs=class_hvs, holds=hold_out,
                            phases=phase_out,
                            frame_idx=state.frame_idx
                            + jnp.asarray(n_valid, jnp.int32))
    return scores, fired, gated, sampled, new_state


_STEP_STATIC = ("h", "w", "stride", "nonlinearity", "t_detection",
                "hold_frames", "backend", "adapt", "precision", "adc_lsb",
                "decim", "park_masked", "sensor_axes", "hyperdim_axes")

#: module-level jit: every runner instance shares one trace cache.
super_chunk_step = jax.jit(super_chunk_fn, static_argnames=_STEP_STATIC)

#: the serving twin: identical trace, but the carried
#: :class:`StreamState` (arg 1) is DONATED — XLA aliases it into the
#: step's output state, so a long-running
#: :class:`repro.launch.serve.FleetService` rolls one state allocation
#: forever instead of allocating per chunk. (The super-chunk buffer
#: itself is donated one stage earlier, at the service's ADC-convert
#: jit, where input and output shapes actually alias; no step output
#: matches the ``(S, C, H, W)`` frames, so donating arg 0 here could
#: never be used.) Donated inputs are dead after the call; only the
#: service (which never re-reads its carried state) may use this.
super_chunk_step_donated = jax.jit(super_chunk_fn,
                                   static_argnames=_STEP_STATIC,
                                   donate_argnums=(1,))


def model_geometry(model: HyperSenseModel, W: int, block_d: int,
                   precision: str = "float32"):
    """Class-independent geometry for ``model`` on width-``W`` frames
    (:class:`ScoreGeometry`, or the int twin for the integer precisions —
    ±1 sign-quantized slabs under ``precision="binary"``)."""
    from repro.kernels import ops as kops
    if precision in adc_sim.INT_PRECISIONS:
        return kops.precompute_geometry_int(
            model.B0, model.b, W=W, w=model.w, stride=model.stride,
            block_d=block_d,
            mode="binary" if precision == "binary" else "int8")
    return kops.precompute_geometry(model.B0, model.b, W=W, w=model.w,
                                    stride=model.stride, block_d=block_d)


def model_tiles(model: HyperSenseModel, W: int, block_d: int,
                precision: str = "float32"):
    """Tile precompute for ``model`` on width-``W`` frames (per precision)."""
    from repro.kernels import ops as kops
    geom = model_geometry(model, W, block_d, precision)
    fn = (kops.retile_classes_int if precision in adc_sim.INT_PRECISIONS
          else kops.retile_classes)
    return fn(geom, model.class_hvs)


class StreamRunner:
    """Stateful chunked scorer+gate(+learner). ``process(frames)`` freely.

    The :class:`StreamState` — controller ``hold``, absolute frame index,
    and (with ``adapt``) the live class hypervectors — carries across
    ``process`` calls, so a long stream can be fed incrementally in
    arbitrary slices; every internal step is one fixed-shape jit call
    (tail chunks are padded and masked, so no recompiles).

    ``adapt=None`` (default) is the frozen runtime — bitwise identical to
    the pre-online-learning runner on the ``pallas`` backend. With an
    :class:`~repro.core.online.AdaptConfig` the classifier updates every
    chunk; in ``"label"`` mode pass per-frame labels to ``process``. The
    live classifier is ``runner.class_hvs``; :meth:`set_class_hvs`
    installs an external update mid-stream (a jitted ``retile_classes``
    gather on the ``pallas`` backend — never a host-side re-precompute;
    the tile cache is keyed on class-hv *identity*, so stale tiles are
    impossible).

    ``control=`` (a :class:`~repro.core.sensor_control.CaptureConfig`)
    closes the capture loop: the ``ControllerConfig`` rates stop being
    decorative — idle frames are LP-converted at ``base_rate_hz`` only
    (temporal decimation inside the chunk scan; skipped frames can never
    fire), gate bursts capture every frame, and the gated frames are
    additionally converted at ``control.hp_bits`` into a bounded buffer,
    drained via :meth:`drain_hp` — the runtime's deliverable to the
    downstream backend. Every runner (open- or closed-loop) keeps a
    :attr:`capture_log` of what the ADC actually converted, which
    :func:`repro.core.energy.from_capture_log` bills directly. With
    ``base == active`` rates or ``subsample=False`` the closed-loop
    outputs are bitwise-identical to ``control=None``.
    """

    def __init__(self, model: HyperSenseModel,
                 config: ControllerConfig | None = None, *,
                 chunk_size: int = 32, backend: str = "jnp",
                 t_detection: int | None = None, block_d: int = 512,
                 adc_bits: int | None = None, adc_sigma: float = 0.0,
                 adc_key: Array | int = 0,
                 adapt: AdaptConfig | None = None,
                 precision: str = "float32",
                 control: CaptureConfig | None = None):
        validate_runner_args(chunk_size, adc_bits, adc_sigma, precision)
        if adapt is not None and adapt.scope == "per-stream":
            raise ValueError('scope="per-stream" is a FleetRunner mode; '
                             "a StreamRunner has exactly one stream — "
                             'use scope="shared"')
        self.precision = precision
        self.model = model
        self.config = config or ControllerConfig()
        self.chunk_size = chunk_size
        self.backend = backend
        self.block_d = block_d
        self.t_detection = (model.t_detection if t_detection is None
                            else t_detection)
        self.adc_bits = adc_bits
        self.adc_sigma = adc_sigma
        self._adc_key = (jax.random.PRNGKey(adc_key)
                         if isinstance(adc_key, int) else adc_key)
        self.adapt = adapt
        self.control = control
        self._decim = (None if control is None
                       else (decimation(self.config) if control.subsample
                             else 1))
        self._geom = None       # (W, ScoreGeometry) — class-independent
        self._tiles = None      # (W, class_hvs-ref, ScoreTiles) frozen path
        self._state = init_stream_state(model.class_hvs, 1)
        self._n_seen = 0        # absolute frame index (keys the ADC noise)
        self._log_sampled: list[np.ndarray] = []
        self._log_gated: list[np.ndarray] = []
        self._frame_pixels = 0
        self._frame_hw: tuple[int, int] | None = None
        self._hp_idx: list[int] = []
        self._hp_frames: list[np.ndarray] = []
        self.hp_dropped = 0     # burst frames lost to a full HP buffer

    def reset(self) -> None:
        self._state = init_stream_state(self.model.class_hvs, 1)
        self._n_seen = 0
        self._tiles = None
        self._log_sampled = []
        self._log_gated = []
        self._hp_idx = []
        self._hp_frames = []
        self.hp_dropped = 0

    @property
    def class_hvs(self) -> Array:
        """The live classifier (updates under ``adapt``)."""
        return self._state.class_hvs

    @property
    def _hold(self) -> Array:   # back-compat scalar view of the gate state
        return self._state.holds[0]

    def set_class_hvs(self, class_hvs: Array) -> None:
        """Install an externally updated classifier mid-stream.

        Device-side cost only: the next chunk re-tiles via the jitted
        ``retile_classes`` gather against the cached geometry (the frozen
        tile cache self-invalidates — it is keyed on class-hv identity).
        """
        class_hvs = jnp.asarray(class_hvs)
        self.model = self.model._replace(class_hvs=class_hvs)
        self._state = dataclasses.replace(self._state,
                                          class_hvs=class_hvs)

    def _ensure_geom(self, W: int):
        if self._geom is None or self._geom[0] != W:
            self._geom = (W, model_geometry(self.model, W, self.block_d,
                                            self.precision))
        return self._geom[1]

    def _ensure_tiles(self, W: int):
        """Frozen-path tile cache, keyed on (width, class-hv identity)."""
        from repro.kernels import ops as kops
        retile = (kops.retile_classes_int
                  if self.precision in adc_sim.INT_PRECISIONS
                  else kops.retile_classes)
        chvs = self._state.class_hvs
        if (self._tiles is None or self._tiles[0] != W
                or self._tiles[1] is not chvs):
            self._tiles = (W, chvs, retile(self._ensure_geom(W), chvs))
        return self._tiles[2]

    @property
    def _adc_lsb(self) -> float:
        return (adc_sim.lsb(self.adc_bits)
                if self.precision in adc_sim.INT_PRECISIONS else 1.0)

    @property
    def capture_log(self) -> CaptureLog:
        """What the ADC actually converted so far (across ``process``
        calls; cleared by :meth:`reset`) — the billing ground truth for
        :func:`repro.core.energy.from_capture_log`."""
        return assemble_capture_log(self._log_sampled, self._log_gated,
                                    lp_bits=self.adc_bits,
                                    control=self.control,
                                    frame_pixels=self._frame_pixels)

    def drain_hp(self) -> tuple[np.ndarray, np.ndarray]:
        """Take the high-precision burst frames captured so far.

        Returns ``(indices (M,) — absolute frame indices, frames
        (M, H, W) at control.hp_bits)`` and empties the buffer; frames a
        full per-chunk buffer dropped are counted in ``hp_dropped``. An
        empty drain keeps the real ``(0, H, W)`` frame shape
        (:func:`hp_drain_arrays`) so cross-drain concatenation works.
        """
        idx, frames = hp_drain_arrays(
            list(zip(self._hp_idx, self._hp_frames)), self._frame_hw)
        self._hp_idx, self._hp_frames = [], []
        return idx, frames

    def process(self, frames, labels=None
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(n, H, W) frames -> (scores (n,), fired (n,), gated (n,)).

        With ``adc_bits`` set, the scorer sees the low-precision ADC
        capture of each frame (:func:`adc_view`) — the paper's always-on
        path — while the caller keeps the raw high-precision frames for
        whatever the gate lets through. With an integer precision the
        capture stays *integer codes* end to end (:func:`adc_view_codes`
        into the fused int kernel; raw integer input is treated as
        already-converted codes — ``"int4"`` additionally nibble-packs
        at the kernel boundary). ``labels`` (``(n,)`` ints) feeds
        ``adapt.mode == "label"`` updates.
        """
        frames = jnp.asarray(frames)
        raw = frames
        self._frame_pixels = int(frames.shape[-2] * frames.shape[-1])
        self._frame_hw = (int(frames.shape[-2]), int(frames.shape[-1]))
        hp_k = resolve_hp_buffer(self.control, self.chunk_size,
                                 frames.dtype)
        base = self._n_seen
        if self.adapt is not None and self.adapt.mode == "label":
            if labels is None:
                raise ValueError('adapt.mode == "label" needs per-frame '
                                 "labels passed to process()")
            labels = jnp.asarray(labels, jnp.int32)
            if labels.shape != frames.shape[:1]:
                raise ValueError(f"labels shape {labels.shape} != "
                                 f"(n,) = {frames.shape[:1]}")
        if self.precision in adc_sim.INT_PRECISIONS:
            from repro.kernels import ops as kops
            kops.assert_int_datapath_fits(self.adc_bits, *frames.shape[-2:],
                                          self.model.h, self.model.w,
                                          stride=self.model.stride,
                                          block_d=self.block_d,
                                          packed=self.precision == "int4")
            frames = adc_view_codes(frames, self.adc_bits,
                                    sigma=self.adc_sigma,
                                    key=self._adc_key,
                                    start_index=self._n_seen)
        elif self.adc_bits is not None:
            frames = adc_view(frames, self.adc_bits, sigma=self.adc_sigma,
                              key=self._adc_key, start_index=self._n_seen)
        n = frames.shape[0]
        self._n_seen += n
        m = self.model
        if (self.backend == "pallas"
                or self.precision in adc_sim.INT_PRECISIONS):
            tiles = (self._ensure_geom(frames.shape[-1])
                     if self.adapt is not None
                     else self._ensure_tiles(frames.shape[-1]))
        else:
            tiles = None
        scores = np.empty(n, np.float32)
        fired = np.empty(n, bool)
        gated = np.empty(n, bool)
        for start in range(0, n, self.chunk_size):
            chunk = frames[start:start + self.chunk_size]
            lab = (labels[start:start + self.chunk_size]
                   if labels is not None
                   else jnp.zeros(chunk.shape[0], jnp.int32))
            n_valid = chunk.shape[0]
            if n_valid < self.chunk_size:
                pad = self.chunk_size - n_valid
                chunk = jnp.pad(chunk, ((0, pad), (0, 0), (0, 0)))
                lab = jnp.pad(lab, (0, pad))
            s, f, g, smp, new_state = super_chunk_step(
                chunk[None], self._state, m.B0, m.b, tiles,
                jnp.float32(m.t_score), jnp.int32(n_valid), lab[None],
                h=m.h, w=m.w, stride=m.stride,
                nonlinearity=m.nonlinearity, t_detection=self.t_detection,
                hold_frames=self.config.hold_frames, backend=self.backend,
                adapt=self.adapt, precision=self.precision,
                adc_lsb=self._adc_lsb, decim=self._decim)
            if self.adapt is None:
                # keep the ORIGINAL class-hv ref: values are untouched and
                # the identity-keyed tile cache must not churn
                new_state = dataclasses.replace(
                    new_state, class_hvs=self._state.class_hvs)
            self._state = new_state
            sl = slice(start, start + n_valid)
            scores[sl] = np.asarray(s)[0, :n_valid]
            fired[sl] = np.asarray(f)[0, :n_valid]
            gated[sl] = np.asarray(g)[0, :n_valid]
            self._log_sampled.append(np.asarray(smp)[0, :n_valid])
            self._log_gated.append(gated[sl].copy())
            if hp_k > 0:
                raw_chunk = raw[start:start + self.chunk_size]
                if n_valid < self.chunk_size:
                    raw_chunk = jnp.pad(
                        raw_chunk,
                        ((0, self.chunk_size - n_valid), (0, 0), (0, 0)))
                entries, dropped = collect_hp(
                    raw_chunk[None], g, n_valid, hp_k,
                    self.control.hp_bits, base + start)
                self._hp_idx.extend(i for i, _ in entries[0])
                self._hp_frames.extend(f for _, f in entries[0])
                self.hp_dropped += dropped
        return scores, fired, gated


def simulate_stream_batched(model: HyperSenseModel, frames, labels,
                            config: ControllerConfig | None = None, *,
                            chunk_size: int = 32, backend: str = "jnp",
                            t_detection: int | None = None,
                            block_d: int = 512,
                            adc_bits: int | None = None,
                            adc_sigma: float = 0.0,
                            adc_key: Array | int = 0,
                            adapt: AdaptConfig | None = None,
                            precision: str = "float32",
                            control: CaptureConfig | None = None
                            ) -> StreamStats:
    """Chunked-batched twin of ``sensor_control.simulate_stream``.

    Produces identical :class:`StreamStats` to replaying
    ``hypersense.detect`` frame-at-a-time through ``SensorController``,
    but runs ``len(frames)/chunk_size`` jitted steps instead of
    ``len(frames)`` dispatches (one kernel launch per chunk on the
    ``pallas`` backend). ``adc_bits`` puts the simulated low-precision
    ADC in front of the gate (pass raw frames). ``adapt`` switches on
    online learning — in ``"label"`` mode the ground-truth ``labels``
    double as the feedback signal.
    """
    runner = StreamRunner(model, config, chunk_size=chunk_size,
                          backend=backend, t_detection=t_detection,
                          block_d=block_d, adc_bits=adc_bits,
                          adc_sigma=adc_sigma, adc_key=adc_key,
                          adapt=adapt, precision=precision,
                          control=control)
    feed = (labels if adapt is not None and adapt.mode == "label"
            else None)
    _, fired, gated = runner.process(frames, labels=feed)
    return stats_from(fired, gated, labels)
