"""Whole-recording replay through the one gate runtime.

:class:`FleetRunner` and :class:`StreamRunner` feed a recorded
``(S, n, H, W)`` fleet (or one ``(n, H, W)`` stream) through a
:class:`~repro.launch.serve.FleetService`: the recording is cut into
``chunk_size`` ticks, the last one short, dispatched, flushed, and the
per-sensor outputs stitched back into ``(S, n)`` arrays. State carries
across ``process`` calls, so a recording fed in any slices gives the
same outputs as fed at once (chunk boundaries move with the slices, which
only an adapting classifier can see). They are offline drivers for
examples, CPU benchmarks and tests; the served path is the service itself.

:func:`simulate_fleet` and :func:`simulate_stream_batched` run one
recording end to end and return the fleet report or the stream's
:class:`~repro.core.sensor_control.StreamStats`.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core import energy
from repro.core.hypersense import HyperSenseModel
from repro.core.online import AdaptConfig
from repro.core.sensor_control import (CaptureConfig, CaptureLog,
                                       ControllerConfig, StreamStats,
                                       stats_from)
from repro.launch.serve import FleetService
from repro.sensing.fleet import FleetReport, fleet_report
from repro.sensing.stream import hp_drain_arrays, validate_runner_args

Array = jax.Array


class FleetRunner:
    """Replay ``(S, n, H, W)`` recordings: ``process`` incrementally.

    Takes :class:`~repro.launch.serve.FleetService`'s configuration
    (``n_slots`` aside). The service is built on the first ``process``
    call — S is known then, and the current mesh is read — with sensors
    ``0..S-1`` attached in order, so row ``s`` is sensor uid ``s`` and
    its ADC noise is keyed by ``fold_in(adc_key, s)``.
    """

    def __init__(self, model: HyperSenseModel,
                 config: ControllerConfig | None = None, *,
                 chunk_size: int = 32, **kw):
        validate_runner_args(chunk_size, kw.get("adc_bits"),
                             kw.get("adc_sigma", 0.0),
                             kw.get("precision", "float32"))
        self.model = model
        self.config = config
        self.chunk_size = chunk_size
        self.adapt: AdaptConfig | None = kw.get("adapt")
        self._kw = kw
        self.service: FleetService | None = None
        self._S = 0

    @property
    def holds(self) -> Array | None:
        """(S,) controller hold state after the last processed frame."""
        if self.service is None:
            return None
        return self.service._state.holds[:self._S]

    @property
    def class_hvs(self) -> Array:
        """The live classifier: ``(2, D)`` shared, ``(S, 2, D)`` per-stream
        (before the first ``process`` call: the model's)."""
        if self.service is None:
            return self.model.class_hvs
        chvs = self.service._state.class_hvs
        return chvs[:self._S] if chvs.ndim == 3 else chvs

    @property
    def hp_dropped(self) -> int:
        """Burst frames lost to full per-chunk HP buffers, fleet-wide."""
        return 0 if self.service is None else self.service.hp_dropped

    @property
    def capture_log(self) -> CaptureLog:
        """(S, N) record of what each stream's ADC actually converted —
        the billing ground truth :func:`fleet_report` prefers."""
        logs = [self.service.capture_log(s) for s in range(self._S)]
        return dataclasses.replace(
            logs[0], sampled=np.stack([g.sampled for g in logs]),
            gated=np.stack([g.gated for g in logs]))

    def drain_hp(self) -> list[tuple[np.ndarray, np.ndarray]]:
        """One ``(indices (M_s,), frames (M_s, H, W))`` pair per stream,
        taken from the service's HP buffers (``[]`` before any frame)."""
        return [self.service.drain_hp(s) for s in range(self._S)]

    def process(self, frames, labels=None
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(S, n, H, W) recording -> ((S, n) scores, fired, gated).

        ``labels`` (``(S, n)`` ints) feeds ``adapt.mode == "label"``
        updates.
        """
        frames = np.asarray(frames)
        if frames.ndim != 4 or frames.shape[1] == 0:
            raise ValueError(f"expected (S, n >= 1, H, W) frames, "
                             f"got shape {frames.shape}")
        S, n = frames.shape[:2]
        label_mode = self.adapt is not None and self.adapt.mode == "label"
        if label_mode:
            if labels is None:
                raise ValueError('adapt.mode == "label" needs per-frame '
                                 "labels passed to process()")
            labels = np.asarray(labels, np.int32)
            if labels.shape != (S, n):
                raise ValueError(f"labels shape {labels.shape} != "
                                 f"(S, n) = {(S, n)}")
        if self.service is None:
            self.service = FleetService(self.model, self.config, n_slots=S,
                                        chunk_size=self.chunk_size,
                                        **self._kw)
            for s in range(S):
                self.service.attach(s)
            self._S = S
        elif S != self._S:
            raise ValueError(f"fleet size changed: carried state has "
                             f"{self._S} streams, got {S}")
        C = self.chunk_size
        for a in range(0, n, C):
            self.service.dispatch(
                {s: frames[s, a:a + C] for s in range(S)},
                {s: labels[s, a:a + C] for s in range(S)}
                if label_mode else None)
        ticks = [ch.outputs for ch in self.service.flush()]
        return tuple(np.stack([np.concatenate([t[s][j] for t in ticks])
                               for s in range(S)])
                     for j in range(3))


class StreamRunner(FleetRunner):
    """One stream: the S = 1 view of :class:`FleetRunner`, ``(n, H, W)``
    in and ``(n,)`` out. Its ADC noise is sensor 0's:
    ``fold_in(adc_key, 0)``."""

    def __init__(self, model: HyperSenseModel,
                 config: ControllerConfig | None = None, **kw):
        adapt = kw.get("adapt")
        if adapt is not None and adapt.scope == "per-stream":
            raise ValueError('scope="per-stream" is a FleetRunner mode; '
                             "a StreamRunner has exactly one stream — "
                             'use scope="shared"')
        super().__init__(model, config, **kw)

    @property
    def capture_log(self) -> CaptureLog:
        return self.service.capture_log(0)

    def drain_hp(self) -> tuple[np.ndarray, np.ndarray]:
        if self.service is None:
            return hp_drain_arrays([], None)
        return self.service.drain_hp(0)

    def process(self, frames, labels=None
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(n, H, W) frames -> (scores (n,), fired (n,), gated (n,))."""
        out = super().process(np.asarray(frames)[None],
                              None if labels is None
                              else np.asarray(labels)[None])
        return tuple(o[0] for o in out)


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
    from the capture log (the per-frame conversions actually made — with
    ``control=`` the closed loop's savings are real Joules here, not a
    duty-cycle estimate). ``adapt`` switches on online learning; in
    ``"label"`` mode the ground-truth ``labels`` double as the feedback
    signal.
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
