"""Set-up and the measured window of one cell, through the served path.

The window drives only the entry points a deployment calls:
``FleetService.attach`` / ``dispatch`` / ``collect`` / ``drain_hp`` and,
in a cascade cell, ``CascadeService.submit`` / ``collect`` / ``flush``.
Everything else here is the benchmark's own: traffic, weights, clocks and
the record of what came back, which the check compares with the reference
once the window has closed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import resource
import time

import jax
import numpy as np

from bench import generator, reference
from bench.reference import gate as gate_ref

#: ticks served before the window: they compile every program the window
#: runs (the gate step, the ADC convert, the HP capture, the backbone)
WARMUP_TICKS = 2

#: seconds of the window a ``--trace 1`` run records with the profiler
TRACE_SECONDS = 4.0

#: share of high-precision frames whose pixels are kept for the check
HP_KEEP = 16


class CompileCounter:
    """Counts JAX traces, lowerings and backend compiles while active."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


@dataclasses.dataclass
class Tick:
    t: int
    dispatched: float
    due: float | None = None
    dispatch_s: float = 0.0
    collected: float | None = None
    collect_s: float = 0.0
    hp: int = 0                     # HP frames captured in this tick
    hp_in_window: int = 0           # of them, logits on the host in time
    c0: float = 0.0                 # when the collect of this tick began
    usage: tuple = (0.0, 0.0)       # process_usage() when collected


@dataclasses.dataclass
class Window:
    """What the window offered and what came back, on the host clock."""
    t0: float
    end: float
    ticks: dict                     # t -> Tick
    compiles: dict
    trace_dir: str | None = None
    trace_t0: float | None = None
    trace_t1: float | None = None
    generator_late_s: list = dataclasses.field(default_factory=list)


class Session:
    """One cell's system under test, its inputs and the record of outputs."""

    def __init__(self, cell, seed: int):
        self.cell = cell
        self.seed = seed
        cfg = cell.config
        self.g = cfg["gate"]
        self.d = cfg.get("detector")
        self.t = cell.traffic
        self.setup = {}
        self.scores, self.fired, self.gated, self.sampled = [], [], [], []
        self.hp_idx = []            # (sid, abs indices) of every HP drain
        self.hp_frames = {}         # (sid, abs_idx) -> frame, a sample
        self.logits = {}            # (sid, abs_idx) -> (n_out,) logits
        self.cascade_batches = 0
        self.batch_log = []         # (host time, real frames) per batch
        self.svc = self.casc = self.det_params = None
        self.mesh = None
        self._end = float("inf")

    # ------------------------------------------------------------------
    # set-up
    # ------------------------------------------------------------------

    @contextlib.contextmanager
    def _step(self, name):
        t0 = time.perf_counter()
        yield
        self.setup[name] = time.perf_counter() - t0

    def build(self) -> None:
        from repro.core.hypersense import HyperSenseModel
        from repro.core.sensor_control import CaptureConfig, ControllerConfig
        from repro.launch.serve import FleetService

        g, t, cfg = self.g, self.t, self.cell.config
        hw = (g["frame_h"], g["frame_w"])
        with self._step("stream_pool"):
            self.pool, self.labels = generator.radar_pool(
                self.seed, t.pool_streams, t.pool_frames, hw, t.event_prob,
                t.event_len)
            self.replay = generator.Replay(self.pool, t)
        with self._step("gate_training"):
            weights = gate_ref.make_weights(self.seed, g, cfg["training"])
        r, tr = cfg["rates"], cfg["training"]
        with self._step("threshold"):
            self.pool_scores = gate_ref.score_frames(
                self.pool, weights, g).reshape(self.labels.shape)
            if "target_duty" in tr:
                self.weights = gate_ref.with_duty(
                    weights, self.pool_scores, r["hold_frames"],
                    max(1, round(r["active_rate_hz"] / r["base_rate_hz"])),
                    tr["target_duty"])
            else:
                self.weights = gate_ref.with_threshold(
                    weights, self.pool_scores[~self.labels],
                    tr["target_fpr"])
        model = HyperSenseModel(
            self.weights.class_hvs, self.weights.B0, self.weights.b,
            g["fragment"], g["fragment"], g["stride"], self.weights.t_score,
            g["t_detection"], g["nonlinearity"])
        with self._step("services"), self._mesh():
            self.svc = FleetService(
                model, ControllerConfig(r["base_rate_hz"], r["active_rate_hz"],
                                        r["hold_frames"]),
                n_slots=t.sensors, chunk_size=t.chunk, backend=g["backend"],
                precision=g["datapath"], adc_bits=g["adc_low_bits"],
                block_d=g["block_d"],
                control=(CaptureConfig(hp_bits=g["adc_high_bits"])
                         if t.capture else None),
                max_inflight=t.max_inflight)
            for sid in range(t.sensors):
                self.svc.attach(sid)
        if self.d is not None:
            self._build_cascade()
        with self._step("warmup_ticks"):
            for k in range(WARMUP_TICKS):
                self.svc.dispatch(self.replay.arrivals(k))
                self._on_gate(self.svc.collect(), None)
            self._flush_cascade(None)
            if self.casc is not None and self.casc.batches == 0:
                self.casc.eager(np.zeros((1, *hw), np.float32))
        self.next_tick = WARMUP_TICKS
        # what set-up made lives on: keep it out of the window's garbage
        # collections, which then walk only what the window allocates
        gc.collect()
        gc.freeze()

    def _mesh(self):
        shape = self.cell.config.get("mesh")
        if not shape:
            return contextlib.nullcontext()
        from repro.distributed import sharding as shlib
        from repro.launch.mesh import make_mesh
        self.mesh = make_mesh(tuple(shape), ("data", "model"))
        return shlib.use_mesh(self.mesh)

    def _build_cascade(self) -> None:
        from repro.configs.base import ModelConfig
        from repro.launch.cascade import CascadeService

        d, g = self.d, self.g
        det_ref = reference.detector(self.cell.config)
        with self._step("detector_init"):
            self.det_params = det_ref.make_weights(
                generator.jax_key(self.seed, 20), g, d)
            jax.block_until_ready(self.det_params)
        with self._step("cascade_build"):
            self.casc = CascadeService(
                self.det_params, ModelConfig(**{
                    f.name: d[f.name] for f in dataclasses.fields(ModelConfig)
                    if f.name in d}),
                batch_size=d["batch"], frame_hw=(g["frame_h"], g["frame_w"]),
                patch=d["patch"], n_out=d["n_out"],
                max_inflight=d["max_inflight"])

    # ------------------------------------------------------------------
    # outputs
    # ------------------------------------------------------------------

    def _on_gate(self, chunk, rec: Tick | None) -> None:
        """Record a collected tick and take its HP frames."""
        S = self.t.sensors
        self.scores.append(np.stack([chunk.outputs[s][0] for s in range(S)]))
        self.fired.append(np.stack([chunk.outputs[s][1] for s in range(S)]))
        self.gated.append(np.stack([chunk.outputs[s][2] for s in range(S)]))
        self.sampled.append(np.stack([chunk.sampled[s] for s in range(S)]))
        if not self.t.capture:
            return
        n = 0
        for sid in self.svc.attached:
            idx, frames = self.svc.drain_hp(sid)
            n += idx.shape[0]
            self.hp_idx.append((sid, idx))
            for j in np.flatnonzero(self._keep(sid, idx)):
                self.hp_frames[(sid, int(idx[j]))] = frames[j]
            if self.casc is not None:
                self.casc.submit(sid, idx, frames)
        if rec is not None:
            rec.hp = n

    def _keep(self, sid: int, idx: np.ndarray) -> np.ndarray:
        """Which of a drain's frames the check keeps: a hash of the seed,
        the sensor and the frame index picks one in :data:`HP_KEEP`."""
        h = (idx.astype(np.uint64) * np.uint64(0x9E3779B1)
             + np.uint64(sid * 0x85EBCA77 + self.seed % 2**32)) \
            & np.uint64(0xFFFFFFFF)
        return (h >> np.uint64(7)) % np.uint64(HP_KEEP) == 0

    def _on_batch(self, batch, ticks: dict | None) -> None:
        now = time.perf_counter()
        self.cascade_batches += 1
        self.batch_log.append((now, len(batch.sids)))
        C = self.t.chunk
        for sid, i, row in zip(batch.sids, batch.frame_idx.tolist(),
                               batch.logits):
            self.logits[(sid, i)] = row
            if ticks is not None and i // C in ticks:
                ticks[i // C].hp_in_window += now <= self._end

    def _flush_cascade(self, ticks) -> None:
        if self.casc is None:
            return
        for b in self.casc.flush():
            self._on_batch(b, ticks)

    # ------------------------------------------------------------------
    # the window
    # ------------------------------------------------------------------

    def run(self, seconds: float, trace_dir: str | None) -> Window:
        counter = CompileCounter()
        span = _Spans(trace_dir is not None)
        ticks: dict[int, Tick] = {}
        t0 = time.perf_counter()
        self._end = t0 + seconds
        win = Window(t0=t0, end=t0 + seconds, ticks=ticks,
                     compiles=counter.counts, trace_dir=trace_dir)
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
            win.trace_t0 = time.perf_counter()
            span.open_window()
        counter.active = True
        try:
            if self.t.loop == "open":
                self._open_loop(win, span)
            else:
                self._closed_loop(win, span)
        finally:
            counter.active = False
            counter.close()
            if trace_dir is not None and win.trace_t1 is None:
                self._stop_trace(win, span)
        return win

    def _stop_trace(self, win: Window, span) -> None:
        span.close_window()
        win.trace_t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def _maybe_stop_trace(self, win: Window, span) -> None:
        if (win.trace_dir is not None and win.trace_t1 is None
                and time.perf_counter() - win.trace_t0 >= TRACE_SECONDS):
            self._stop_trace(win, span)

    def _gate_collected(self, chunk, ticks, span) -> None:
        rec = ticks[chunk.seq]
        rec.collected = time.perf_counter()
        rec.collect_s = rec.collected - rec.c0
        rec.usage = process_usage()
        with span("bench.pump" if self.casc is not None else "bench.drain"):
            self._on_gate(chunk, rec)
            # keep at most the cascade's in-flight bound of batches out
            while self.casc is not None and self.casc.batches \
                    - self.cascade_batches > self.casc.max_inflight:
                self._on_batch(self.casc.collect(), ticks)

    def _closed_loop(self, win: Window, span) -> None:
        svc, ticks = self.svc, win.ticks
        inflight = []
        while time.perf_counter() < win.end:
            t = self.next_tick
            rec = Tick(t=t, dispatched=time.perf_counter())
            with span("bench.dispatch"):
                svc.dispatch(self.replay.arrivals(t))
            rec.dispatch_s = time.perf_counter() - rec.dispatched
            ticks[t] = rec
            inflight.append(t)
            self.next_tick += 1
            if len(inflight) >= self.t.max_inflight:
                ticks[inflight.pop(0)].c0 = time.perf_counter()
                with span("bench.collect"):
                    chunk = svc.collect()
                self._gate_collected(chunk, ticks, span)
            self._maybe_stop_trace(win, span)
        for t in inflight:
            ticks[t].c0 = time.perf_counter()
        for chunk in svc.flush():
            self._gate_collected(chunk, ticks, span)
        self._flush_cascade(ticks)

    def _open_loop(self, win: Window, span) -> None:
        svc, ticks = self.svc, win.ticks
        due = generator.open_schedule(win.t0, self.t.period_s,
                                      win.end - win.t0)
        for d in due:
            with span("bench.wait"):
                _sleep_until(d)
            t = self.next_tick
            rec = Tick(t=t, dispatched=time.perf_counter(), due=float(d))
            win.generator_late_s.append(rec.dispatched - d)
            with span("bench.dispatch"):
                svc.dispatch(self.replay.arrivals(t))
            rec.dispatch_s = time.perf_counter() - rec.dispatched
            ticks[t] = rec
            self.next_tick += 1
            rec.c0 = time.perf_counter()
            with span("bench.collect"):
                chunk = svc.collect()
            self._gate_collected(chunk, ticks, span)
            self._maybe_stop_trace(win, span)
        self._flush_cascade(ticks)

    # ------------------------------------------------------------------

    def memory_peak(self) -> int:
        devs = self.mesh.devices.flat if self.mesh is not None \
            else jax.devices()[:1]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devs]
        return int(max(peaks))

    def free_program(self) -> None:
        """Drop the services, so the reference runs with their state gone."""
        self.svc = self.casc = None
        gc.unfreeze()
        gc.collect()


def process_usage() -> tuple:
    """CPU seconds this process has used so far, ``(user, system)``."""
    r = resource.getrusage(resource.RUSAGE_SELF)
    return (r.ru_utime, r.ru_stime)


def _sleep_until(deadline: float) -> None:
    while True:
        left = deadline - time.perf_counter()
        if left <= 0:
            return
        time.sleep(left - 0.001 if left > 0.002 else 0)


class _Spans:
    """Host spans in the profiler's trace, only while tracing."""

    def __init__(self, on: bool):
        self.on = on
        self._window = None

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def open_window(self):
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()

    def close_window(self):
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None
            self.on = False
