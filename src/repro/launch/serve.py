"""Always-on fleet serving: async double-buffered ingestion + slot churn.

The paper's "Intelligent Sensor Control" system is *continuously
running*: ADC streams feed the HDC gate in real time, and the FPGA
design wins end-to-end because data movement overlaps compute.
:class:`FleetService` is the one runtime of the gate step
(:func:`repro.sensing.stream.super_chunk_fn`): it builds the step, its
tiles and ADC front-end, pads its slot axis to the mesh, and owns the HP
capture and the per-sensor capture logs. Whole-recording replay
(:mod:`repro.launch.replay`) and the gated cascade
(:mod:`repro.launch.cascade`) sit on top of it.

**Double buffering** (:meth:`FleetService.dispatch` /
:meth:`~FleetService.collect`). ``dispatch`` assembles the next
super-chunk on host, ``jax.device_put``'s it (H2D copy begins
immediately), and launches the jitted fleet step — which, under JAX's
async dispatch, returns the instant the work is *enqueued*. The host is
already assembling and transferring tick ``t+1`` while the device still
executes tick ``t``: the send/await split of a DMA frame manager, at the
host↔device boundary (the in-kernel analog is the double-buffered DMA
pattern in the Pallas guide). ``collect`` blocks only on the *oldest*
in-flight chunk. The host super-chunks rotate too: at most
``max_inflight + 1`` buffers, each back in the pool once its tick is
collected, so a tick never maps and zeroes fresh pages. The rotating
device buffers are **donated** where they can alias: the raw
super-chunk into the ADC-convert jit (float in, float out — same
buffer), and the carried
:class:`~repro.sensing.stream.StreamState` into the fleet step
(``super_chunk_step``), so a service that steps forever rolls the same
device allocations instead of growing per chunk.

**Short ticks.** A tick's arrivals share one length ``L <= chunk_size``.
``L`` goes into the step's traced ``n_valid`` operand, so a short tick
(the tail of a recording, say) runs the same compiled step; outputs,
logs and HP captures keep the first ``L`` frames of each arrival.

**Slot-pooled churn** (:meth:`~FleetService.attach` /
:meth:`~FleetService.detach`). The fleet step always runs at a fixed
``(n_slots, chunk_size, H, W)`` shape; sensors map onto slots and
membership/ragged arrival only flips bits in the step's ``slot_mask``
operand — the padded-slot machinery, reused as a pool. Churn
therefore NEVER changes an array shape and never triggers a recompile
(:meth:`~FleetService.compile_count` exposes the step's XLA compile
counter so callers can assert exactly that). The step parks a masked
slot's hold/phase/classifier state in place,
and detach parks the slot's state host-side, so detach→reattach —
even through an intervening tenant in the same slot — restores a
sensor's adapted classifier, gate hold, ADC phase, and capture log
bitwise.

**Checkpointed online state** (:meth:`~FleetService.checkpoint` /
:meth:`~FleetService.restore`). The mutable fleet state — adapted
``class_hvs``, holds, phases, the slot table, parked sensors, per-sensor
capture logs — snapshots through
:class:`repro.ckpt.checkpoint.AsyncCheckpointer` (write happens on a
background thread; ``ckpt_every=N`` automates it per N chunks). Restore
into a freshly constructed service resumes the trace bitwise-identical
to an uninterrupted run (``tests/test_serve.py``).

**Telemetry** (:mod:`repro.launch.telemetry`). Each tick records spans
under its sequence number: ``serve.dispatch`` (up to the enqueue; the
back-pressure collect and the checkpoint come after it) and its parts
``.assemble`` (the host super-chunk), ``.upload`` (enqueueing dispatch's
``device_put`` calls, which return before the bytes have moved) and
``.launch`` (key fold, ADC convert, fleet-step enqueue; a compile shows
here); ``serve.collect`` and its parts ``.wait`` (the host reads of the
step's outputs) and ``.hp_capture``. Counters: ``serve.h2d_bytes``, every
array the service puts on the device (dispatch's, and the raw
super-chunk the HP capture uploads again), ``serve.arrival_frames``
delivered, ``serve.scored_frames`` (slot-frames the step ran),
``serve.strided_align_frames`` (of them, scored by a kernel that aligns
its band products with one strided lane rotate; derived from the step's
backend, precision and frame width through the kernel's own choice,
:func:`repro.kernels.sliding_scores.strided_alignment`, not read back
from the compiled kernel),
``serve.sampled_frames`` (of them, converted by an active sensor's LP
ADC), ``serve.hp_frames`` captured and ``serve.assemble_reused`` (ticks
assembled into a recycled host buffer: every tick after the first
``max_inflight + 1``). None of it waits for the device.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Hashable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt import checkpoint as ckpt_mod
from repro.core.hypersense import HyperSenseModel
from repro.core.online import AdaptConfig
from repro.core.sensor_control import (CaptureConfig, CaptureLog,
                                       ControllerConfig,
                                       assemble_capture_log, decimation)
from repro.distributed import sharding as shlib
from repro.kernels import sliding_scores as kernel_ss
from repro.launch import telemetry
from repro.sensing import adc as adc_sim
from repro.sensing import fleet as fleet_mod
from repro.sensing import stream as stream_mod
from repro.sensing.stream import StreamState, init_stream_state

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ServedChunk:
    """One collected tick: per-sensor outputs.

    ``outputs[sid]`` is ``(scores (L,), fired (L,), gated (L,))`` numpy
    arrays for every sensor that delivered frames in the tick (``L`` the
    tick's arrival length);
    ``sampled[sid]`` marks the frames its LP ADC actually converted
    (closed-loop mode). Where the host's time went for the tick is in
    :mod:`repro.launch.telemetry`, under the tick's ``seq``.
    """
    seq: int
    outputs: dict[Hashable, tuple[np.ndarray, np.ndarray, np.ndarray]]
    sampled: dict[Hashable, np.ndarray]


@dataclasses.dataclass
class _Parked:
    """Per-sensor state parked across detach (or never-yet-attached)."""
    uid: int
    n_seen: int
    hold: Any          # i32 scalar (device array — may still be in flight)
    phase: Any
    class_hvs: Any     # (2, D) in per-stream scope, else None


@dataclasses.dataclass
class _InFlight:
    """A dispatched, not-yet-collected tick (device futures + host meta)."""
    seq: int
    scores: Array
    fired: Array
    gated: Array
    sampled: Array
    sids: tuple                      # slot -> sid for arrival slots, else None
    starts: np.ndarray               # (S,) per-slot absolute frame base
    host: np.ndarray                 # the pooled host raw super-chunk
    n_valid: int                     # frames per arrival in the tick


def _adc_convert_fn(frames: Array, keys: Array, starts: Array, *,
                    bits: int, sigma: float, codes: bool) -> Array:
    """Per-slot ADC front-end: one fused async unit ahead of the step.

    Each slot converts with its OWN noise key (folded per persistent
    sensor uid, not slot index) and its own absolute frame base, so a
    sensor's capture is bit-identical no matter which slot it lands in
    or how its stream interleaves with churn.
    """
    view = stream_mod.adc_view_codes if codes else stream_mod.adc_view
    return jax.vmap(lambda f, k, s0: view(f, bits, sigma=sigma, key=k,
                                          start_index=s0))(
                                              frames, keys, starts)


_ADC_STATIC = ("bits", "sigma", "codes")
#: float->float conversion aliases in place: the rotating raw super-chunk
#: buffer (fresh ``device_put`` each tick) is donated into its LP view.
_adc_convert = jax.jit(_adc_convert_fn, donate_argnums=(0,),
                       static_argnames=_ADC_STATIC)
#: float->integer codes cannot alias (dtype change) — no donation.
_adc_convert_codes = jax.jit(_adc_convert_fn, static_argnames=_ADC_STATIC)

#: uid-keyed noise: one key per slot, folded from the service key by the
#: slot's persistent sensor uid. Module-jitted so every dispatch tick
#: reuses one cache entry instead of building a fresh vmap per tick.
_fold_uid_keys = jax.jit(jax.vmap(jax.random.fold_in, in_axes=(None, 0)))


class FleetService:
    """Slot-pooled, double-buffered, checkpointed fleet serving.

    The one runtime of the gate step: sensors :meth:`attach` /
    :meth:`detach` dynamically (capacity is a fixed ``n_slots`` pool,
    rounded up to the mesh's "sensors" extent so the padded slot axis
    always shards), each service *tick* is one :meth:`dispatch` of up
    to ``chunk_size`` frames from whichever sensors have them ready
    (ragged arrival = absent from the dict), and :meth:`collect` returns
    finished ticks in FIFO order. Up to ``max_inflight`` ticks pipeline
    between host and device; state (classifier adaptation, gate
    hysteresis, closed-loop ADC phase) carries from tick to tick.

    Config: ``backend``, ``precision``, ``adc_bits``/``adc_sigma``/
    ``adc_key``, ``adapt``, ``control``, ``mesh`` (default: the current
    :func:`repro.distributed.sharding.use_mesh` mesh), plus:

    * ``n_slots`` — pool capacity;
    * ``max_inflight`` — dispatched-but-uncollected ticks before
      ``dispatch`` itself drains the oldest (back-pressure);
    * ``ckpt_dir`` / ``ckpt_every`` / ``ckpt_keep`` — automatic async
      snapshots of the mutable fleet state every N ticks.

    Sensor ids must be JSON-serializable scalars (``str`` or ``int``) —
    they ride the checkpoint manifest.
    """

    def __init__(self, model: HyperSenseModel,
                 config: ControllerConfig | None = None, *,
                 n_slots: int, chunk_size: int = 32, backend: str = "jnp",
                 t_detection: int | None = None, block_d: int = 512,
                 adc_bits: int | None = None, adc_sigma: float = 0.0,
                 adc_key: Array | int = 0, mesh=None,
                 adapt: AdaptConfig | None = None,
                 precision: str = "float32",
                 control: CaptureConfig | None = None,
                 max_inflight: int = 2,
                 ckpt_dir: str | None = None, ckpt_every: int = 0,
                 ckpt_keep: int = 3):
        stream_mod.validate_runner_args(chunk_size, adc_bits, adc_sigma,
                                        precision)
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, "
                             f"got {max_inflight}")
        if ckpt_every and ckpt_dir is None:
            raise ValueError("ckpt_every > 0 needs ckpt_dir")
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
        self.precision = precision
        self.control = control
        self._decim = (None if control is None
                       else (decimation(self.config) if control.subsample
                             else 1))
        self.max_inflight = max_inflight
        self._mesh = mesh if mesh is not None else shlib.current_mesh()
        # capacity is padded ONCE: churn never re-pads, shapes never move
        self.n_slots = shlib.padded_extent(n_slots, "sensors", self._mesh)
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self._ckpt = (ckpt_mod.AsyncCheckpointer(ckpt_dir, keep=ckpt_keep)
                      if ckpt_dir is not None else None)

        self._slots: list = [None] * self.n_slots   # slot -> sid
        self._by_sid: dict = {}                     # sid -> slot
        self._uids: dict = {}                       # sid -> persistent uid
        self._n_seen: dict = {}                     # sid -> abs frame count
        self._parked: dict[Any, _Parked] = {}
        self._logs: dict = {}      # sid -> (sampled blocks, gated blocks)
        self._hp: dict = {}        # sid -> [(abs_idx, frame), ...]
        self.hp_dropped = 0
        self._next_uid = 0
        self._seq = 0              # ticks dispatched so far
        self._frame_hw: tuple[int, int] | None = None
        self._frame_pixels = 0
        self._geom = None
        self._tiles = None
        self._step = None
        self._step_axes = None     # ("sensors", "hyperdim") axes at build
        self._n_valid = jnp.int32(chunk_size)
        self._t_score = jnp.float32(model.t_score)
        # donated state rotates through the step forever — seed it with a
        # COPY so the model's own class_hvs buffer is never invalidated
        self._state = init_stream_state(
            jnp.array(np.asarray(model.class_hvs)), self.n_slots,
            per_stream=self._per_stream())
        self._pending: collections.deque[_InFlight] = collections.deque()
        self._ready: collections.deque[ServedChunk] = collections.deque()
        # free host super-chunk buffers of one dtype; with the in-flight
        # ticks' own, at most max_inflight + 1 exist
        self._host_free: list[np.ndarray] = []
        self._host_dtype: np.dtype | None = None

    # ------------------------------------------------------------------
    # slot pool
    # ------------------------------------------------------------------

    def _per_stream(self) -> bool:
        return self.adapt is not None and self.adapt.scope == "per-stream"

    @property
    def attached(self) -> tuple:
        """Currently attached sensor ids, in slot order."""
        return tuple(sid for sid in self._slots if sid is not None)

    @property
    def free_slots(self) -> int:
        return sum(1 for sid in self._slots if sid is None)

    def uid(self, sid) -> int:
        """Persistent per-sensor uid (keys the ADC noise stream; survives
        detach/reattach and checkpoint/restore)."""
        return self._uids[sid]

    def attach(self, sid) -> int:
        """Claim a slot for ``sid``; returns the slot index.

        A previously detached sensor resumes its parked state — adapted
        classifier row, gate hold, ADC phase, frame counter, capture
        log — bitwise, even if other tenants used the slot meanwhile.
        """
        if not isinstance(sid, (str, int)):
            raise TypeError(f"sensor id must be str or int (rides the "
                            f"checkpoint manifest), got {type(sid)}")
        if sid in self._by_sid:
            raise ValueError(f"sensor {sid!r} already attached")
        try:
            slot = self._slots.index(None)
        except ValueError:
            raise RuntimeError(
                f"slot pool exhausted ({self.n_slots} slots, "
                f"{len(self._parked)} parked): detach a sensor or build "
                f"the service with more n_slots") from None
        st = self._state
        if sid in self._parked:
            p = self._parked.pop(sid)
            holds = st.holds.at[slot].set(p.hold)
            phases = st.phases.at[slot].set(p.phase)
            chvs = (st.class_hvs.at[slot].set(p.class_hvs)
                    if p.class_hvs is not None else st.class_hvs)
            self._n_seen[sid] = p.n_seen
            self._uids[sid] = p.uid
        else:
            holds = st.holds.at[slot].set(0)
            phases = st.phases.at[slot].set(0)
            chvs = (st.class_hvs.at[slot].set(self.model.class_hvs)
                    if st.class_hvs.ndim == 3 else st.class_hvs)
            self._n_seen[sid] = 0
            self._uids[sid] = self._next_uid
            self._next_uid += 1
            self._logs[sid] = ([], [])
            self._hp[sid] = []
        self._state = StreamState(class_hvs=chvs, holds=holds,
                                  phases=phases, frame_idx=st.frame_idx)
        self._slots[slot] = sid
        self._by_sid[sid] = slot
        return slot

    def detach(self, sid) -> None:
        """Release ``sid``'s slot, parking its state for reattach.

        Park is lazy device slices of the carried state — no pipeline
        sync: in-flight ticks keep executing and the parked values
        resolve whenever they are next needed.
        """
        slot = self._by_sid.pop(sid, None)
        if slot is None:
            raise ValueError(f"sensor {sid!r} is not attached")
        st = self._state
        self._parked[sid] = _Parked(
            uid=self._uids[sid], n_seen=self._n_seen[sid],
            hold=st.holds[slot], phase=st.phases[slot],
            class_hvs=(st.class_hvs[slot] if st.class_hvs.ndim == 3
                       else None))
        self._slots[slot] = None

    # ------------------------------------------------------------------
    # step plumbing
    # ------------------------------------------------------------------

    def _ensure_geom(self, W: int):
        if self._geom is None:
            self._geom = stream_mod.model_geometry(
                self.model, W, self.block_d, self.precision)
        return self._geom

    def _ensure_tiles(self, W: int):
        if self._tiles is None:
            self._tiles = stream_mod.model_tiles(
                self.model, W, self.block_d, self.precision)
        return self._tiles

    def _ensure_step(self, W: int):
        """Build (once) the fleet step + tile args."""
        if self.backend == "pallas" \
                or self.precision in adc_sim.INT_PRECISIONS:
            tiles = (self._ensure_geom(W) if self.adapt is not None
                     else self._ensure_tiles(W))
        else:
            tiles = None
        if self._step is None:
            m = self.model
            axes = fleet_mod._sensor_axes(self._mesh)[0]
            hd_axes = fleet_mod._hyperdim_axes(self._mesh, tiles,
                                               self.backend, self.precision)
            self._step = fleet_mod._build_step(
                self._mesh, axes, hd_axes,
                fleet_mod._tiles_specs(tiles, hd_axes),
                h=m.h, w=m.w, stride=m.stride,
                nonlinearity=m.nonlinearity, t_detection=self.t_detection,
                hold_frames=self.config.hold_frames, backend=self.backend,
                adapt=self.adapt, precision=self.precision,
                adc_lsb=self._adc_lsb, decim=self._decim)
            self._step_axes = (axes, hd_axes)
        return self._step, tiles

    @property
    def _adc_lsb(self) -> float:
        return (adc_sim.lsb(self.adc_bits)
                if self.precision in adc_sim.INT_PRECISIONS else 1.0)

    def compile_count(self) -> int:
        """Cumulative XLA compilations of this service's step function.

        The churn contract's witness: after the warm-up tick, attach/
        detach/ragged arrival/short ticks must leave this number frozen
        (asserted by ``tests/test_serve.py``). Unsharded services share
        the module-level step's cache, so compare DELTAS around a trace,
        not absolutes.
        """
        step = self._step
        if step is None:
            return 0
        fn = step.func if isinstance(step, functools.partial) else step
        return fn._cache_size()

    def compiled_step_text(self) -> str:
        """HLO text of the fleet step compiled at this service's shapes.

        The witness of where scoring runs: a kernel compiled for the TPU
        appears as a ``tpu_custom_call``; an interpreted one does not.
        Needs a dispatched tick (the first arrival fixes the frame shape).
        """
        if self._frame_hw is None:
            raise RuntimeError("dispatch a tick first: the frame shape "
                               "fixes the step")
        step, tiles = self._ensure_step(self._frame_hw[1])
        fn, kw = ((step.func, step.keywords)
                  if isinstance(step, functools.partial) else (step, {}))
        S, C = self.n_slots, self.chunk_size
        dtype = (adc_sim.codes_dtype(self.adc_bits)
                 if self.precision in adc_sim.INT_PRECISIONS
                 else jnp.float32)
        sds = jax.ShapeDtypeStruct
        m = self.model
        return fn.lower(sds((S, C, *self._frame_hw), dtype), self._state,
                        m.B0, m.b, tiles, self._t_score, self._n_valid,
                        sds((S, C), jnp.int32), sds((S,), jnp.bool_),
                        **kw).compile().as_text()

    def _host_buffer(self, shape: tuple, dtype) -> np.ndarray:
        """A host super-chunk for the next tick: a collected tick's
        buffer, else a new one. A dtype change drops the pool."""
        dtype = np.dtype(dtype)
        if dtype != self._host_dtype:
            self._host_free, self._host_dtype = [], dtype
        if self._host_free:
            telemetry.count("serve.assemble_reused")
            return self._host_free.pop()
        return np.empty(shape, dtype)

    def _put(self, x, spec=None):
        telemetry.count("serve.h2d_bytes", x.nbytes)
        if self._mesh is None or spec is None:
            return jax.device_put(x)
        return jax.device_put(x, NamedSharding(self._mesh, spec))

    # ------------------------------------------------------------------
    # dispatch / collect
    # ------------------------------------------------------------------

    def dispatch(self, arrivals: dict, labels: dict | None = None) -> int:
        """Enqueue one service tick; returns its sequence number.

        ``arrivals`` maps attached sensor ids to ``(L, H, W)`` frame
        blocks (raw float frames, or integer ADC codes under an integer
        precision), one ``L <= chunk_size`` for every arrival of the
        tick — a tick that mixes lengths is refused. An attached sensor
        absent from the dict is masked for the tick — its carried state
        is parked in place, as if no time passed for it. ``labels``
        (same keying, ``(L,)`` ints) feeds ``adapt.mode == "label"``
        updates.

        Returns as soon as the H2D transfer and the fleet step are
        *enqueued*; compute for up to ``max_inflight`` ticks overlaps
        the host assembling + transferring the next ones. Results come
        back through :meth:`collect`, oldest first.
        """
        with telemetry.span("serve.dispatch", self._seq):
            C, S = self.chunk_size, self.n_slots
            label_mode = (self.adapt is not None
                          and self.adapt.mode == "label")
            if labels is not None and not label_mode:
                raise ValueError(
                    "labels passed without adapt.mode == 'label'")
            # shape peeks only — np.shape reads .shape without pulling a
            # device arrival to host (the upload happens once, batched)
            shape = None
            for sid, fr in arrivals.items():
                if sid not in self._by_sid:
                    raise ValueError(f"sensor {sid!r} is not attached")
                shp = tuple(int(d) for d in np.shape(fr))
                if shape is not None and shp != shape:
                    raise ValueError(
                        f"arrival for {sid!r} has shape {shp}, expected "
                        f"{shape}: the arrivals of a tick share one length")
                shape = shp
            if shape is not None and (
                    len(shape) != 3 or not 1 <= shape[0] <= C
                    or shape[1:] != (self._frame_hw or shape[1:])):
                raise ValueError(
                    f"arrival shape {shape}, expected (L, H, W) with "
                    f"1 <= L <= chunk_size = {C}"
                    + (f" and (H, W) = {self._frame_hw}"
                       if self._frame_hw else ""))
            L = shape[0] if shape else C     # a silent tick runs a chunk
            if shape is not None and self._frame_hw is None:
                self._frame_hw = shape[1:]
                self._frame_pixels = (self._frame_hw[0]
                                      * self._frame_hw[1])
                if self.precision in adc_sim.INT_PRECISIONS:
                    from repro.kernels import ops as kops
                    kops.assert_int_datapath_fits(
                        self.adc_bits, *self._frame_hw, self.model.h,
                        self.model.w, stride=self.model.stride,
                        block_d=self.block_d,
                        packed=self.precision == "int4")
            if self._frame_hw is None:
                raise ValueError("first dispatch needs at least one "
                                 "arrival to fix the frame shape")
            H, W = self._frame_hw

            int_codes = (self.precision in adc_sim.INT_PRECISIONS
                         and all(np.issubdtype(np.result_type(f),
                                               np.integer)
                                 for f in arrivals.values()) and arrivals)
            with telemetry.span("serve.dispatch.assemble"):
                assemble = self._host_buffer(
                    (S, C, H, W), np.int32 if int_codes else np.float32)
                mask_np = np.zeros((S,), bool)
                starts = np.zeros((S,), np.int32)
                uids = np.zeros((S,), np.int32)
                lab_np = np.zeros((S, C), np.int32)
                # refuses integer codes where the HP capture needs raw
                # frames, before the tick is enqueued
                stream_mod.resolve_hp_buffer(
                    self.control, C, assemble.dtype)
                for sid, fr in arrivals.items():
                    slot = self._by_sid[sid]
                    # repro-lint: disable=RA003 (admission boundary: ragged arrivals are normalized into the host assemble buffer, then uploaded once, batched)
                    assemble[slot, :L] = np.asarray(fr)
                    mask_np[slot] = True
                    starts[slot] = self._n_seen[sid]
                    uids[slot] = self._uids[sid]
                    self._n_seen[sid] += L
                    if label_mode:
                        if labels is None or sid not in labels:
                            raise ValueError(
                                f'adapt.mode == "label": arrival for '
                                f"{sid!r} needs labels[{sid!r}]")
                        # repro-lint: disable=RA003 (labels are caller-side host metadata, folded into the batched upload)
                        lab_np[slot, :L] = np.asarray(labels[sid], np.int32)
                # a recycled buffer holds an older tick's frames: masked
                # slots and a short tick's tail see zeros, as in a fresh
                # buffer
                assemble[~mask_np] = 0
                if L < C:
                    assemble[:, L:] = 0
            telemetry.count("serve.arrival_frames", L * len(arrivals))

            axes = self._step_axes[0] if self._step_axes else \
                fleet_mod._sensor_axes(self._mesh)[0]
            s4 = P(axes, None, None, None) if axes else None
            s2 = P(axes, None) if axes else None
            s1 = P(axes) if axes else None
            convert = self.adc_bits is not None and not int_codes
            with telemetry.span("serve.dispatch.upload"):
                frames = self._put(assemble, s4)  # H2D begins here, async
                mask = self._put(mask_np, s1)
                lab = self._put(lab_np, s2)
                if convert:
                    uids_d = self._put(uids, s1)
                    starts_d = self._put(starts, s1)

            with telemetry.span("serve.dispatch.launch"):
                if int_codes:
                    # already-converted codes: concrete range check +
                    # pack (the noise, if configured, applies before
                    # conversion — integer input with sigma > 0 raises)
                    frames = stream_mod.adc_view_codes(
                        frames, self.adc_bits, sigma=self.adc_sigma)
                elif convert:
                    keys = _fold_uid_keys(self._adc_key, uids_d)
                    codes = self.precision in adc_sim.INT_PRECISIONS
                    conv = _adc_convert_codes if codes else _adc_convert
                    frames = conv(frames, keys, starts_d,
                                  bits=self.adc_bits, sigma=self.adc_sigma,
                                  codes=codes)

                step, tiles = self._ensure_step(W)
                m = self.model
                n_valid = self._n_valid if L == C else jnp.int32(L)
                s, f, g, smp, new_state = step(
                    frames, self._state, m.B0, m.b, tiles, self._t_score,
                    n_valid, lab, mask)
            telemetry.count("serve.scored_frames", S * C)
            # the same function scores_from_tiles asks; int4 is the one
            # precision the step feeds the kernel packed
            if self.backend == "pallas" and kernel_ss.strided_alignment(
                    W, packed=self.precision == "int4"):
                telemetry.count("serve.strided_align_frames", S * C)
            self._state = new_state
            self._seq += 1
            rec = _InFlight(
                seq=self._seq - 1, scores=s, fired=f, gated=g, sampled=smp,
                sids=tuple(sid if mask_np[i] else None
                           for i, sid in enumerate(self._slots)),
                starts=starts, host=assemble, n_valid=L)
            self._pending.append(rec)
        # back-pressure and the checkpoint sit outside ``serve.dispatch``:
        # a tick collected here records its own top-level ``serve.collect``
        while len(self._pending) > self.max_inflight:
            self._ready.append(self._finish(self._pending.popleft()))
        if self.ckpt_every and self._seq % self.ckpt_every == 0:
            self.checkpoint()
        return rec.seq

    def _finish(self, rec: _InFlight) -> ServedChunk:
        with telemetry.span("serve.collect", rec.seq):
            # collect IS the deliberate sync point of the pipeline: these
            # block only on the OLDEST in-flight tick, after max_inflight
            # newer ticks were already enqueued behind it.
            with telemetry.span("serve.collect.wait"):
                s = np.asarray(rec.scores)  # repro-lint: disable=RA003 (designed sync point: blocks on the oldest in-flight tick only)
                f = np.asarray(rec.fired)  # repro-lint: disable=RA003 (same designed sync point)
                g = np.asarray(rec.gated)  # repro-lint: disable=RA003 (same designed sync point)
                smp = np.asarray(rec.sampled)  # repro-lint: disable=RA003 (same designed sync point)
            L = rec.n_valid
            telemetry.count("serve.sampled_frames", smp[:, :L].sum())
            outputs, sampled = {}, {}
            for slot, sid in enumerate(rec.sids):
                if sid is None:
                    continue
                outputs[sid] = (s[slot, :L], f[slot, :L], g[slot, :L])
                sampled[sid] = smp[slot, :L]
                logs = self._logs[sid]
                logs[0].append(smp[slot, :L])
                logs[1].append(g[slot, :L])
            hp_k = stream_mod.resolve_hp_buffer(self.control,
                                                self.chunk_size,
                                                rec.host.dtype)
            if hp_k > 0:
                with telemetry.span("serve.collect.hp_capture"):
                    # a detached-but-still-holding slot's gated output is
                    # masked noise — it must not be HP-captured or counted
                    # as dropped
                    act = np.array([sid is not None for sid in rec.sids])
                    # the raw super-chunk goes up again: dispatch's copy
                    # was donated into the ADC convert
                    entries, dropped = stream_mod.collect_hp(
                        self._put(rec.host), self._put(g & act[:, None]),
                        L, hp_k, self.control.hp_bits, rec.starts)
                    for slot, sid in enumerate(rec.sids):
                        if sid is not None:
                            self._hp[sid].extend(entries[slot])
                            telemetry.count("serve.hp_frames",
                                            len(entries[slot]))
                self.hp_dropped += dropped
            # the step that consumed this tick's upload has finished (its
            # outputs were read above), and the HP capture has read the
            # raw frames: every backend's device_put of the buffer is
            # complete, so the next tick may write it
            if rec.host.dtype == self._host_dtype:
                self._host_free.append(rec.host)
            return ServedChunk(seq=rec.seq, outputs=outputs, sampled=sampled)

    def collect(self) -> ServedChunk | None:
        """Oldest finished tick (FIFO), or None when nothing is in flight.

        Blocks only until the oldest dispatched tick's outputs are
        host-resident — younger ticks keep executing behind it.
        """
        if self._ready:
            return self._ready.popleft()
        if not self._pending:
            return None
        return self._finish(self._pending.popleft())

    def flush(self) -> list[ServedChunk]:
        """Drain every in-flight tick (in order) — a full pipeline sync."""
        out = list(self._ready)
        self._ready.clear()
        while self._pending:
            out.append(self._finish(self._pending.popleft()))
        return out

    # ------------------------------------------------------------------
    # per-sensor views
    # ------------------------------------------------------------------

    def class_hvs_of(self, sid) -> np.ndarray:
        """The live ``(2, D)`` classifier serving ``sid`` (parked or
        attached). Shared scope returns the fleet classifier."""
        if self._state.class_hvs.ndim == 2:
            return np.asarray(self._state.class_hvs)
        if sid in self._parked:
            return np.asarray(self._parked[sid].class_hvs)
        return np.asarray(self._state.class_hvs[self._by_sid[sid]])

    def capture_log(self, sid) -> CaptureLog:
        """What ``sid``'s ADC actually converted so far (per-sensor
        billing ground truth; survives detach and checkpoint/restore)."""
        blocks = self._logs[sid]
        return assemble_capture_log(blocks[0], blocks[1],
                                    lp_bits=self.adc_bits,
                                    control=self.control,
                                    frame_pixels=self._frame_pixels)

    def drain_hp(self, sid) -> tuple[np.ndarray, np.ndarray]:
        """Take ``sid``'s high-precision burst frames captured so far
        (absolute frame indices + frames at ``control.hp_bits``). An
        empty drain keeps the real ``(0, H, W)`` frame shape
        (:func:`~repro.sensing.stream.hp_drain_arrays`) so cross-drain
        concatenation works — the cascade's contract."""
        idx, frames = stream_mod.hp_drain_arrays(self._hp[sid],
                                                 self._frame_hw)
        self._hp[sid] = []
        return idx, frames

    # ------------------------------------------------------------------
    # checkpoint / restore
    # ------------------------------------------------------------------

    def _snapshot(self) -> tuple[dict, dict]:
        """(single-level array tree, JSON extra) of the mutable state."""
        st = self._state
        tree = {"class_hvs": st.class_hvs, "holds": st.holds,
                "phases": st.phases, "frame_idx": st.frame_idx}
        parked_sids = list(self._parked)
        for i, sid in enumerate(parked_sids):
            p = self._parked[sid]
            tree[f"parked_hold_{i}"] = p.hold
            tree[f"parked_phase_{i}"] = p.phase
            if p.class_hvs is not None:
                tree[f"parked_chvs_{i}"] = p.class_hvs
        log_sids = list(self._logs)
        for i, sid in enumerate(log_sids):
            blocks = self._logs[sid]
            tree[f"log_sampled_{i}"] = (np.concatenate(blocks[0])
                                        if blocks[0]
                                        else np.zeros((0,), bool))
            tree[f"log_gated_{i}"] = (np.concatenate(blocks[1])
                                      if blocks[1]
                                      else np.zeros((0,), bool))
            # undrained HP burst frames ride the checkpoint too: the
            # cascade's deliverable must survive kill-and-resume, not
            # just the billing that accounts for it
            hp_idx, hp_frames = stream_mod.hp_drain_arrays(
                self._hp.get(sid, []), self._frame_hw)
            tree[f"hp_idx_{i}"] = hp_idx
            tree[f"hp_frames_{i}"] = hp_frames
        extra = {
            "chunks": self._seq,
            "slots": [[i, sid, self._uids[sid], self._n_seen[sid]]
                      for i, sid in enumerate(self._slots)
                      if sid is not None],
            "parked": [[sid, p.uid, p.n_seen,
                        f"parked_chvs_{i}" in tree]
                       for i, (sid, p) in enumerate(self._parked.items())],
            "log_sids": log_sids,
            "next_uid": self._next_uid,
            "frame_hw": list(self._frame_hw) if self._frame_hw else None,
            "n_slots": self.n_slots,
            "precision": self.precision,
        }
        return tree, extra

    def checkpoint(self) -> None:
        """Async snapshot of the mutable fleet state.

        Drains the in-flight pipeline into the ready queue first (their
        outputs stay collectable) so the saved state, frame counters and
        capture logs all describe the same tick boundary; the disk write
        then happens on the checkpointer's background thread while
        serving continues.
        """
        if self._ckpt is None:
            raise RuntimeError("service was built without ckpt_dir")
        while self._pending:
            self._ready.append(self._finish(self._pending.popleft()))
        tree, extra = self._snapshot()
        self._ckpt.save(self._seq, tree, extra=extra)

    def wait_ckpt(self) -> None:
        """Block until the last async checkpoint write is on disk."""
        if self._ckpt is not None:
            self._ckpt.wait()

    def restore(self, step: int | None = None) -> int:
        """Load fleet state from ``ckpt_dir`` into this (fresh) service.

        Rebuilds the slot table, parked pool, per-sensor counters and
        capture logs, and installs the saved ``StreamState`` — resuming
        the trace from the returned tick count is bitwise-identical to
        never having stopped (``tests/test_serve.py`` pins this on both
        backends). Construct the service with the SAME model/config as
        the saved run.
        """
        if self._ckpt is None:
            raise RuntimeError("service was built without ckpt_dir")
        if self._seq:
            raise RuntimeError("restore() needs a freshly constructed "
                               "service (no ticks dispatched)")
        leaves, extra = ckpt_mod.restore_tree(self.ckpt_dir, step=step)
        if extra["n_slots"] != self.n_slots:
            raise ValueError(f"checkpoint has n_slots={extra['n_slots']}, "
                             f"service has {self.n_slots}")
        if extra["precision"] != self.precision:
            raise ValueError(f"checkpoint precision {extra['precision']} "
                             f"!= service {self.precision}")
        self._state = StreamState(
            class_hvs=jnp.asarray(leaves["class_hvs"]),
            holds=jnp.asarray(leaves["holds"]),
            phases=jnp.asarray(leaves["phases"]),
            frame_idx=jnp.asarray(leaves["frame_idx"]))
        self._slots = [None] * self.n_slots
        self._by_sid, self._uids, self._n_seen = {}, {}, {}
        for slot, sid, uid, n_seen in extra["slots"]:
            self._slots[slot] = sid
            self._by_sid[sid] = slot
            self._uids[sid] = uid
            self._n_seen[sid] = n_seen
        self._parked = {}
        for i, (sid, uid, n_seen, has_chvs) in enumerate(extra["parked"]):
            self._parked[sid] = _Parked(
                uid=uid, n_seen=n_seen,
                hold=jnp.asarray(leaves[f"parked_hold_{i}"]),
                phase=jnp.asarray(leaves[f"parked_phase_{i}"]),
                class_hvs=(jnp.asarray(leaves[f"parked_chvs_{i}"])
                           if has_chvs else None))
            self._uids[sid] = uid
            self._n_seen[sid] = n_seen
        self._logs = {}
        self._hp = {}
        for i, sid in enumerate(extra["log_sids"]):
            self._logs[sid] = ([leaves[f"log_sampled_{i}"]]
                               if leaves[f"log_sampled_{i}"].size else [],
                               [leaves[f"log_gated_{i}"]]
                               if leaves[f"log_gated_{i}"].size else [])
            if f"hp_idx_{i}" in leaves:        # absent in pre-cascade ckpts
                self._hp[sid] = list(zip(
                    leaves[f"hp_idx_{i}"].tolist(),
                    leaves[f"hp_frames_{i}"].astype(np.float32)))
            else:
                self._hp[sid] = []
        self._next_uid = extra["next_uid"]
        self._seq = extra["chunks"]
        if extra["frame_hw"]:
            self._frame_hw = tuple(extra["frame_hw"])
            self._frame_pixels = self._frame_hw[0] * self._frame_hw[1]
        return self._seq
