"""Multi-sensor fleet streaming runtime (paper §I: escalating sensor counts).

HyperSense's always-on HDC front-end is fleet-scale in deployment — one
edge site aggregates many radar/camera feeds (cf. Eggimann et al.'s
always-on SCM accelerator, HyperCam's camera fleets). This module
multiplies the single-stream chunked runtime (:mod:`repro.sensing.stream`)
along a sensor axis without multiplying kernel launches:

* ``(S, C, H, W)`` **super-chunks** — S concurrent streams, C frames each —
  are flattened to an ``S*C`` batch and scored by ONE ``pallas_call``
  (grid ``(S*C, n_dt)``) against one shared
  :class:`~repro.kernels.sliding_scores.ScoreTiles` precompute
  (:func:`repro.kernels.ops.fragment_score_map_fleet`);
* per-stream controller hysteresis is ``vmap(gate_scan)`` — S independent
  ``lax.scan`` hold states carried across super-chunks, so every stream
  sees exactly the gating an independent :class:`StreamRunner` would give;
* the optional low-precision **ADC** sits in front of the gate
  (``adc_bits=4`` reproduces the paper's Fig. 3 loop: the gate scores the
  cheap capture, the caller keeps the raw frames for gated-on delivery);
* the fleet step is **sharded across a 2-D device mesh** with
  ``shard_map`` via the logical-axis rules in
  :mod:`repro.distributed.sharding`: "sensors" partitions S over the
  data-parallel axes (padded with masked slots when S doesn't divide —
  never an unsharded fallback) and "hyperdim" partitions the D-tile axis
  of slabs + class tiles over "model" (one order-preserving all_gather in
  the score epilogue; shared-scope online updates all_gather their
  samples and fold replicated). Every mesh shape is bitwise-identical to
  the unsharded runner; without a mesh the exact same code runs
  unsharded — CPU tests are unchanged.

:func:`fleet_report` turns the per-stream gate decisions into per-stream
:class:`~repro.core.sensor_control.StreamStats` plus a fleet-aggregate
energy account built on :mod:`repro.core.energy`.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import energy
from repro.core.hypersense import HyperSenseModel
from repro.core.online import AdaptConfig
from repro.core.sensor_control import (CaptureConfig, CaptureLog,
                                       ControllerConfig, StreamStats,
                                       assemble_capture_log, decimation,
                                       stats_from_batch)
from repro.distributed import sharding as shlib
from repro.sensing import adc as adc_sim
from repro.sensing import stream as stream_mod
from repro.sensing.stream import (StreamState, adc_view, adc_view_codes,
                                  init_stream_state, model_geometry,
                                  super_chunk_fn, super_chunk_step)

Array = jax.Array


def _sensor_axes(mesh) -> tuple[tuple[str, ...] | None, int]:
    """("sensors" mesh axes or None, their total extent k).

    Padding-aware: resolved via :func:`repro.distributed.sharding.
    mesh_extent`, which keeps non-divisible axes — the fleet pads S up
    to a multiple of ``k`` with masked slots instead of ever falling
    back to an unsharded step.
    """
    if mesh is None:
        return None, 1
    axes, k = shlib.mesh_extent("sensors", mesh)
    return (axes or None), k


def _hyperdim_axes(mesh, tiles, backend: str,
                   precision: str) -> tuple[str, ...] | None:
    """Mesh axes the "hyperdim" (D-tile) dim shards over, or None.

    The float ``jnp`` backend has no tiled scorer, so only the
    ``pallas`` backend and the integer precisions (whose jnp oracle is
    tiled) can partition D. A tile count the mesh extent doesn't divide
    falls back to replicated tiles (the :func:`spec_for` divisibility
    rule) — sensors-only sharding still applies.
    """
    if mesh is None or tiles is None:
        return None
    if backend != "pallas" and precision not in adc_sim.INT_PRECISIONS:
        return None
    geom = getattr(tiles, "geom", tiles)
    slabs = geom.slabs_q if hasattr(geom, "slabs_q") else geom.slabs
    part = shlib.spec_for((slabs.shape[0],), ("hyperdim",), mesh)
    if not part or part[0] is None:
        return None
    ax = part[0]
    return ax if isinstance(ax, tuple) else (ax,)


def _tiles_specs(tiles, hd: tuple[str, ...] | None):
    """PartitionSpec pytree for the step's ``tiles`` argument.

    Only the D-tile-leading arrays (slabs, bias/idx/valid, class tiles)
    shard over the hyperdim axes; scales and the full-D class
    norms stay replicated — norms ARE full-D quantities, which is what
    keeps the sharded cosine epilogue exact. Built by
    ``dataclasses.replace`` on the live tiles instance so static fields
    (and hence the pytree structure) match the argument exactly.
    """
    if tiles is None:
        return None
    hd3 = P(hd, None, None) if hd else P()
    rep = P()

    def geom_specs(g):
        if hasattr(g, "slabs_q"):
            return dataclasses.replace(g, slabs_q=hd3, bias_t=hd3, idx=hd3,
                                       valid=hd3, slab_scale=rep)
        return dataclasses.replace(g, slabs=hd3, bias_t=hd3, idx=hd3,
                                   valid=hd3)

    if hasattr(tiles, "geom"):
        cls = (P(None, hd, None, None) if hd else P()) \
            if tiles.cpos_t.ndim == 4 else hd3
        return dataclasses.replace(tiles, geom=geom_specs(tiles.geom),
                                   cpos_t=cls, cneg_t=cls,
                                   cpos_norm=rep, cneg_norm=rep)
    return geom_specs(tiles)


def _build_step(mesh, axes, hd_axes, tiles_spec, donate: bool = False,
                **static):
    """Fleet step callable: the shared module-level jit, or shard_map'd.

    Unsharded, this is just :func:`repro.sensing.stream.super_chunk_step`
    with the static config bound — every runner shares its global trace
    cache. ``donate=True`` (the always-on serving layer,
    :class:`repro.launch.serve.FleetService`) switches to the donated
    twin ``super_chunk_step_donated``: the carried ``StreamState``
    pytree is donated to XLA so a service that steps forever rolls one
    state allocation instead of reallocating per chunk — callers must
    never re-read a donated input after the call.
    Under a mesh, the raw step body is ``shard_map``'d over BOTH
    logical axes — sensors (streams partition like a batch) and hyperdim
    (each device holds a contiguous D-shard of slabs + class tiles) —
    and jitted per (mesh, axes, tiles structure).

    Collectives, all inside the step body and all order-preserving:

    * the scorer's tile fold all_gathers per-tile partials over
      ``hd_axes`` before a fixed left-to-right reduction
      (``sliding_scores._ordered_tile_fold``) — bitwise-equal to the
      single-device epilogue;
    * a shared-scope online update all_gathers the masked samples over
      ``axes`` and replays the identical sequential fold on every
      device (``stream.super_chunk_fn._shared_fold``) — the former
      "falls back to unsharded" case, now sharded and still bitwise.

    ``check_vma=False`` because replicated outputs (shared classifiers)
    are produced by identical replicated folds the checker can't see
    through.
    """
    if axes is None and hd_axes is None:
        return functools.partial(
            stream_mod.super_chunk_step_donated if donate
            else super_chunk_step, **static)
    s4, s3, s2, s1 = (P(axes, None, None, None), P(axes, None, None),
                      P(axes, None), P(axes))
    rep = P()
    per_stream = (static.get("adapt") is not None
                  and static["adapt"].scope == "per-stream")
    state_in = StreamState(class_hvs=s3 if per_stream else rep,
                           holds=s1, phases=s1, frame_idx=rep)
    return jax.jit(jax.shard_map(
        functools.partial(super_chunk_fn, sensor_axes=axes,
                          hyperdim_axes=hd_axes, **static), mesh=mesh,
        in_specs=(s4, state_in, rep, rep, tiles_spec, rep, rep, s2, s1),
        out_specs=(s2, s2, s2, s2, state_in),
        check_vma=False), donate_argnums=(1,) if donate else ())


@dataclasses.dataclass(frozen=True)
class FleetReport:
    """Per-stream stats + fleet-aggregate energy accounting."""
    stats: list[StreamStats]              # one per sensor stream
    n_frames: int                         # frames per stream
    duty_cycle: float                     # fleet-mean fraction gated on
    energy_per_frame: energy.EnergyBreakdown  # fleet-mean, HyperSense path
    energy_total_j: float                 # fleet total over all frames
    baseline_total_j: float               # always-on conventional fleet

    @property
    def n_sensors(self) -> int:
        return len(self.stats)

    @property
    def total_saving(self) -> float:
        return 1.0 - self.energy_total_j / self.baseline_total_j


def fleet_report(fired, gated, labels,
                 params: energy.EnergyParams | None = None,
                 precision: str = "float32",
                 capture: CaptureLog | None = None) -> FleetReport:
    """(S, N) gate decisions -> per-stream stats + fleet energy account.

    With a ``capture`` log (the runners maintain one) the fleet is billed
    from what the ADCs *actually* converted and transmitted
    (:func:`repro.core.energy.from_capture_log`) — the primary account:
    closed-loop idle subsampling shows up as real Joules saved, which the
    duty-fraction approximation structurally cannot see. Without one,
    each stream is billed at its own *measured* duty cycle
    (:func:`repro.core.energy.hypersense_measured`, every frame assumed
    LP-converted — exactly what the capture log degenerates to in
    open-loop mode). The baseline is the conventional always-on pipeline
    on every stream. ``precision`` is the datapath the gate actually ran
    on — the integer precisions bill the always-on HDC work at their
    reduced per-precision cost (``EnergyParams.hdc_*_factor``).
    """
    params = params or energy.EnergyParams()
    stats = stats_from_batch(fired, gated, labels)
    n = int(np.asarray(fired).shape[1])
    duty = float(np.mean([s.duty_cycle for s in stats]))
    if capture is not None:
        mean = energy.from_capture_log(capture, params, precision)
        total = mean.total * len(stats) * n
    else:
        per_stream = [energy.hypersense_measured(s.duty_cycle, params,
                                                 precision)
                      for s in stats]
        total = sum(b.total for b in per_stream) * n
        mean = energy.hypersense_measured(duty, params, precision)
    base = energy.conventional(params).total * len(stats) * n
    return FleetReport(stats=stats, n_frames=n, duty_cycle=duty,
                       energy_per_frame=mean, energy_total_j=float(total),
                       baseline_total_j=float(base))


class FleetRunner:
    """Stateful fleet scorer+gate: ``process((S, n, H, W))`` incrementally.

    Semantically S independent :class:`~repro.sensing.stream.StreamRunner`
    instances — per-stream scores/fired/gated are asserted identical in
    ``tests/test_fleet.py`` — executed as one batched pipeline: each
    ``(S, chunk_size)`` super-chunk is a single jitted step (one kernel
    launch on the ``pallas`` backend) and the ``(S,)`` hold vector carries
    across ``process`` calls.

    ``adc_bits`` puts the simulated low-precision ADC in front of the
    gate; noise (``adc_sigma > 0``) is keyed per (stream, absolute frame
    index), so stream slicing stays invisible. Under an active
    :func:`repro.distributed.sharding.use_mesh` (or an explicit ``mesh=``)
    the sensor axis is ``shard_map``'d across the mesh axes the "sensors"
    rule resolves to.

    ``adapt`` switches on online learning
    (:class:`~repro.core.online.AdaptConfig`): ``scope="shared"`` folds
    every stream's samples (time-ordered) into ONE fleet classifier;
    ``scope="per-stream"`` gives each sensor its own ``(S, 2, D)``
    classifier — updates are ``vmap``'d over streams, scoring stays one
    kernel launch (stream-indexed class-tile BlockSpecs), and the sharded
    step continues to partition cleanly (no collectives). Shared-scope
    updates shard too: the step all_gathers every shard's masked samples
    and replays the identical time-ordered fold on each device, so the
    shared classifier stays replicated and bitwise-equal to unsharded.

    ``control=`` (:class:`~repro.core.sensor_control.CaptureConfig`)
    closes each stream's capture loop independently: per-stream
    ``(hold, phase)`` ADC state rides the same sharded
    :class:`~repro.sensing.stream.StreamState` (still no collectives —
    the control scan is per-stream), idle frames are subsampled to
    ``base_rate_hz``, and gated bursts are HP-captured into per-stream
    bounded buffers (:meth:`drain_hp`). The fleet's
    :attr:`capture_log` is the ``(S, N)`` billing ground truth
    :func:`fleet_report` prefers over the duty-cycle approximation.
    """

    def __init__(self, model: HyperSenseModel,
                 config: ControllerConfig | None = None, *,
                 chunk_size: int = 32, backend: str = "jnp",
                 t_detection: int | None = None, block_d: int = 512,
                 adc_bits: int | None = None, adc_sigma: float = 0.0,
                 adc_key: Array | int = 0, mesh=None,
                 adapt: AdaptConfig | None = None,
                 precision: str = "float32",
                 control: CaptureConfig | None = None):
        stream_mod.validate_runner_args(chunk_size, adc_bits, adc_sigma,
                                        precision)
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
        self._mesh = mesh
        self.adapt = adapt
        self.control = control
        self._decim = (None if control is None
                       else (decimation(self.config) if control.subsample
                             else 1))
        self._geom = None       # (W, ScoreGeometry) — class-independent
        self._tiles = None      # (W, class_hvs-ref, ScoreTiles) frozen path
        self._state = None      # StreamState, allocated on first process()
        self._n_seen = 0
        self._step = None
        self._step_key = None
        self._log_sampled: list[np.ndarray] = []   # (S, chunk) blocks
        self._log_gated: list[np.ndarray] = []
        self._frame_pixels = 0
        self._frame_hw: tuple[int, int] | None = None
        self._hp: list[list] = []   # per stream: [(abs_idx, frame), ...]
        self.hp_dropped = 0

    def reset(self) -> None:
        self._state = None
        self._n_seen = 0
        self._tiles = None
        self._log_sampled = []
        self._log_gated = []
        self._hp = []
        self.hp_dropped = 0

    @property
    def holds(self) -> Array | None:
        """(S,) controller hold state after the last processed frame."""
        return None if self._state is None else self._state.holds

    @property
    def class_hvs(self) -> Array:
        """The live classifier: ``(2, D)`` shared, ``(S, 2, D)`` per-stream
        (before the first ``process`` call: the model's)."""
        return (self.model.class_hvs if self._state is None
                else self._state.class_hvs)

    def set_class_hvs(self, class_hvs: Array) -> None:
        """Install an externally updated classifier mid-stream.

        Accepts ``(2, D)`` (broadcast to every stream in per-stream
        scope) or ``(S, 2, D)`` in per-stream scope. Device-side cost
        only — next chunk re-tiles via the jitted ``retile_classes``; the
        identity-keyed tile cache self-invalidates.
        """
        class_hvs = jnp.asarray(class_hvs)
        if class_hvs.ndim == 3 and not self._per_stream():
            raise ValueError("(S, 2, D) classifiers need "
                             'adapt scope="per-stream"')
        if class_hvs.ndim == 2:
            self.model = self.model._replace(class_hvs=class_hvs)
        if self._state is None:
            if class_hvs.ndim == 3:
                # fleet size is fixed by the stack; allocate state now so
                # the per-stream classifiers are not silently dropped
                self._state = init_stream_state(
                    class_hvs, class_hvs.shape[0], per_stream=True)
            return  # ndim == 2: first process() initializes from model
        chvs = class_hvs
        if self._state.class_hvs.ndim == 3 and chvs.ndim == 2:
            chvs = jnp.broadcast_to(chvs, self._state.class_hvs.shape)
        if chvs.shape != self._state.class_hvs.shape:
            raise ValueError(f"class_hvs shape {chvs.shape} != carried "
                             f"state {self._state.class_hvs.shape}")
        self._state = dataclasses.replace(self._state, class_hvs=chvs)

    def _per_stream(self) -> bool:
        return self.adapt is not None and self.adapt.scope == "per-stream"

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
        """(S, N) record of what each stream's ADC actually converted —
        the billing ground truth :func:`fleet_report` prefers."""
        return assemble_capture_log(self._log_sampled, self._log_gated,
                                    lp_bits=self.adc_bits,
                                    control=self.control,
                                    frame_pixels=self._frame_pixels,
                                    axis=1)

    def drain_hp(self) -> list[tuple[np.ndarray, np.ndarray]]:
        """Per-stream HP burst deliverables captured so far.

        Returns one ``(indices (M_s,), frames (M_s, H, W))`` pair per
        stream (absolute frame indices; frames at ``control.hp_bits``)
        and empties the buffers. Per-chunk buffer overflows are counted
        fleet-wide in ``hp_dropped``. Empty drains keep the real
        ``(0, H, W)`` frame shape
        (:func:`~repro.sensing.stream.hp_drain_arrays`) so per-stream
        cross-drain concatenation works.
        """
        out = [stream_mod.hp_drain_arrays(entries, self._frame_hw)
               for entries in self._hp]
        self._hp = [[] for _ in self._hp]
        return out

    def _ensure_step(self, tiles):
        """Step callable + the sensor-axis extent k (S pads to k·⌈S/k⌉).

        Cached per (mesh, resolved axes, adapt config, tiles pytree
        structure) — a new tiles *instance* (every frozen-cache refresh)
        reuses the step as long as its structure is unchanged, so
        sharding never causes per-chunk retraces. Shared-scope
        adaptation shards like everything else (the step all_gathers the
        samples and folds replicated); there is no unsharded fallback.
        """
        mesh = self._mesh if self._mesh is not None else shlib.current_mesh()
        axes, k = _sensor_axes(mesh)
        hd_axes = _hyperdim_axes(mesh, tiles, self.backend, self.precision)
        key = (id(mesh) if (axes or hd_axes) else None, axes, hd_axes,
               self.adapt, jax.tree_util.tree_structure(tiles))
        if self._step is None or self._step_key != key:
            m = self.model
            self._step = _build_step(
                mesh, axes, hd_axes, _tiles_specs(tiles, hd_axes),
                h=m.h, w=m.w, stride=m.stride,
                nonlinearity=m.nonlinearity, t_detection=self.t_detection,
                hold_frames=self.config.hold_frames, backend=self.backend,
                adapt=self.adapt, precision=self.precision,
                adc_lsb=self._adc_lsb, decim=self._decim)
            self._step_key = key
        return self._step, k

    def process(self, frames, labels=None
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(S, n, H, W) super-stream -> ((S, n) scores, fired, gated).

        ``labels`` (``(S, n)`` ints) feeds ``adapt.mode == "label"``
        updates.
        """
        frames = jnp.asarray(frames)
        if frames.ndim != 4:
            raise ValueError(f"expected (S, n, H, W) frames, "
                             f"got shape {frames.shape}")
        S, n = frames.shape[:2]
        raw = frames
        self._frame_pixels = int(frames.shape[-2] * frames.shape[-1])
        self._frame_hw = (int(frames.shape[-2]), int(frames.shape[-1]))
        hp_k = stream_mod.resolve_hp_buffer(self.control, self.chunk_size,
                                            frames.dtype)
        if not self._hp:
            self._hp = [[] for _ in range(S)]
        base = self._n_seen
        if self.adapt is not None and self.adapt.mode == "label":
            if labels is None:
                raise ValueError('adapt.mode == "label" needs per-frame '
                                 "labels passed to process()")
            labels = jnp.asarray(labels, jnp.int32)
            if labels.shape != (S, n):
                raise ValueError(f"labels shape {labels.shape} != "
                                 f"(S, n) = {(S, n)}")
        if self._state is None:
            self._state = init_stream_state(self.model.class_hvs, S,
                                            per_stream=self._per_stream())
        elif self._state.holds.shape[0] != S:
            raise ValueError(f"fleet size changed: carried state has "
                             f"{self._state.holds.shape[0]} streams, "
                             f"got {S}")
        if self.precision in adc_sim.INT_PRECISIONS:
            from repro.kernels import ops as kops
            kops.assert_int_datapath_fits(self.adc_bits, *frames.shape[-2:],
                                          self.model.h, self.model.w,
                                          stride=self.model.stride,
                                          block_d=self.block_d,
                                          packed=self.precision == "int4")
            if jnp.issubdtype(frames.dtype, jnp.integer):
                # already-converted codes: concrete range check + pack
                # (sigma forwarded so configured noise can't silently
                # drop — integer input + sigma > 0 raises, as on
                # StreamRunner)
                frames = adc_view_codes(frames, self.adc_bits,
                                        sigma=self.adc_sigma)
            else:
                keys = jax.vmap(
                    lambda s: jax.random.fold_in(self._adc_key, s))(
                        jnp.arange(S))
                frames = jax.vmap(lambda k, f: adc_view_codes(
                    f, self.adc_bits, sigma=self.adc_sigma, key=k,
                    start_index=self._n_seen))(keys, frames)
        elif self.adc_bits is not None:
            keys = jax.vmap(
                lambda s: jax.random.fold_in(self._adc_key, s))(
                    jnp.arange(S))
            frames = jax.vmap(lambda k, f: adc_view(
                f, self.adc_bits, sigma=self.adc_sigma, key=k,
                start_index=self._n_seen))(keys, frames)
        self._n_seen += n

        m = self.model
        if (self.backend == "pallas"
                or self.precision in adc_sim.INT_PRECISIONS):
            tiles = (self._ensure_geom(frames.shape[-1])
                     if self.adapt is not None
                     else self._ensure_tiles(frames.shape[-1]))
        else:
            tiles = None
        step, k = self._ensure_step(tiles)
        # Pad the sensor axis to the mesh extent with masked slots: the
        # padded step shards for ANY S (never a recompile per S, never an
        # unsharded fallback); masked slots are exact no-ops on every
        # real slot (tests/test_fleet.py pins S=5/S=9 on 8 devices
        # bitwise). Carried state stays at the real S.
        S_pad = -(-S // k) * k
        slot_mask = jnp.arange(S_pad) < S
        scores = np.empty((S, n), np.float32)
        fired = np.empty((S, n), bool)
        gated = np.empty((S, n), bool)
        for start in range(0, n, self.chunk_size):
            chunk = frames[:, start:start + self.chunk_size]
            lab = (labels[:, start:start + self.chunk_size]
                   if labels is not None
                   else jnp.zeros(chunk.shape[:2], jnp.int32))
            n_valid = chunk.shape[1]
            if n_valid < self.chunk_size:
                pad = self.chunk_size - n_valid
                chunk = jnp.pad(chunk, ((0, 0), (0, pad), (0, 0), (0, 0)))
                lab = jnp.pad(lab, ((0, 0), (0, pad)))
            state = self._state
            if S_pad != S:
                pad_s = S_pad - S
                chunk = jnp.pad(chunk,
                                ((0, pad_s),) + ((0, 0),) * 3)
                lab = jnp.pad(lab, ((0, pad_s), (0, 0)))
                chvs = state.class_hvs
                if chvs.ndim == 3:
                    # pad slots carry (discarded) copies of the model's
                    # classifier — real values, so retiling them can
                    # never poison a shared kernel launch with NaNs
                    chvs = jnp.concatenate(
                        [chvs, jnp.broadcast_to(
                            self.model.class_hvs,
                            (pad_s,) + self.model.class_hvs.shape)], 0)
                state = StreamState(
                    class_hvs=chvs,
                    holds=jnp.pad(state.holds, (0, pad_s)),
                    phases=jnp.pad(state.phases, (0, pad_s)),
                    frame_idx=state.frame_idx)
            s, f, g, smp, new_state = step(
                chunk, state, m.B0, m.b, tiles,
                jnp.float32(m.t_score), jnp.int32(n_valid), lab, slot_mask)
            if S_pad != S:
                s, f, g, smp = s[:S], f[:S], g[:S], smp[:S]
                new_state = StreamState(
                    class_hvs=(new_state.class_hvs[:S]
                               if new_state.class_hvs.ndim == 3
                               else new_state.class_hvs),
                    holds=new_state.holds[:S],
                    phases=new_state.phases[:S],
                    frame_idx=new_state.frame_idx)
            if self.adapt is None:
                # keep the ORIGINAL class-hv ref: values are untouched and
                # the identity-keyed tile cache must not churn
                new_state = dataclasses.replace(
                    new_state, class_hvs=self._state.class_hvs)
            self._state = new_state
            sl = slice(start, start + n_valid)
            scores[:, sl] = np.asarray(s)[:, :n_valid]
            fired[:, sl] = np.asarray(f)[:, :n_valid]
            gated[:, sl] = np.asarray(g)[:, :n_valid]
            self._log_sampled.append(np.asarray(smp)[:, :n_valid])
            self._log_gated.append(gated[:, sl].copy())
            if hp_k > 0:
                raw_chunk = raw[:, start:start + self.chunk_size]
                if n_valid < self.chunk_size:
                    raw_chunk = jnp.pad(
                        raw_chunk, ((0, 0), (0, self.chunk_size - n_valid),
                                    (0, 0), (0, 0)))
                entries, dropped = stream_mod.collect_hp(
                    raw_chunk, g, n_valid, hp_k, self.control.hp_bits,
                    base + start)
                for si in range(S):
                    self._hp[si].extend(entries[si])
                self.hp_dropped += dropped
        return scores, fired, gated


def simulate_fleet(model: HyperSenseModel, frames, labels,
                   config: ControllerConfig | None = None, *,
                   chunk_size: int = 32, backend: str = "jnp",
                   t_detection: int | None = None, block_d: int = 512,
                   adc_bits: int | None = None, adc_sigma: float = 0.0,
                   adc_key: Array | int = 0, mesh=None,
                   adapt: AdaptConfig | None = None,
                   energy_params: energy.EnergyParams | None = None,
                   precision: str = "float32",
                   control: CaptureConfig | None = None) -> FleetReport:
    """Run a whole ``(S, N, H, W)`` fleet recording end-to-end.

    One :class:`FleetRunner` pass followed by :func:`fleet_report`:
    per-stream :class:`StreamStats` (identical to S independent
    single-stream simulations) plus the fleet energy account, billed
    from the runner's capture log (the per-frame conversions actually
    made — with ``control=`` the closed loop's savings are real Joules
    here, not a duty-cycle estimate). ``adapt`` switches on online
    learning; in ``"label"`` mode the ground-truth ``labels`` double as
    the feedback signal.
    """
    runner = FleetRunner(model, config, chunk_size=chunk_size,
                         backend=backend, t_detection=t_detection,
                         block_d=block_d, adc_bits=adc_bits,
                         adc_sigma=adc_sigma, adc_key=adc_key, mesh=mesh,
                         adapt=adapt, precision=precision, control=control)
    feed = (labels if adapt is not None and adapt.mode == "label"
            else None)
    _, fired, gated = runner.process(frames, labels=feed)
    return fleet_report(fired, gated, labels, energy_params, precision,
                        capture=runner.capture_log)
