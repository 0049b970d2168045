"""Gated-frame → downstream-backbone cascade serving (the paper's loop).

HyperSense's system claim is gate-then-detect: the always-on HDC gate
runs on low-precision ADC data, and only the frames it passes are
high-precision captured and fed to the heavy downstream detector —
5.6x end-to-end vs an always-on YOLOv4 and up to 92.1% energy saving
(paper §V-E). The gate runtime already produces exactly that feed:
:meth:`repro.launch.serve.FleetService.drain_hp` delivers ``(absolute
frame indices, (M, H, W) HP frames)`` bursts per sensor.
:class:`CascadeService` is the consumer that closes the loop:

* **Fixed-shape batching.** Drains are ragged (a quiet tick drains 0
  frames, a bursty one dozens). Frames queue host-side and launch in
  fixed ``(batch_size, H, W)`` blocks — the tail pads with zero rows
  that are dropped on collect — so the backbone step compiles ONCE and
  ragged drain sizes can never retrace it
  (:meth:`~CascadeService.compile_count` witnesses, same contract as
  ``FleetService``).

* **Batching.** For a backbone without experts the detector step
  (:func:`repro.launch.steps.build_detector_cell`) maps the batch axis
  with ``jax.lax.map``, so a frame's logits are bit-identical whether
  it arrives alone, padded, or co-batched mid-burst — batched service
  output ≡ eager per-frame evaluation (:meth:`~CascadeService.eager`),
  gated in ``benchmarks/fig16_speedup.py --system --check``. A backbone
  with experts runs the whole block as one batch, so its expert layers
  read their weights once a batch; its dropless routing keeps a frame's
  logits free of the other rows, up to the rounding of batched products.

* **Async double-buffering** (PR-8 pattern). ``device_put`` starts the
  H2D copy immediately and the jitted step returns once *enqueued*, so
  backbone compute overlaps the gate's next ticks; up to
  ``max_inflight`` batches pipeline before the oldest is drained
  (back-pressure), and :meth:`~CascadeService.collect` blocks only on
  the oldest in-flight batch. Each batch records the spans
  ``cascade.launch`` (block assembly, upload, step enqueue) and
  ``cascade.wait`` (the host blocked on its logits) in
  :mod:`repro.launch.telemetry`, under the batch's ``seq``, and adds the
  block's bytes to the counter ``cascade.h2d_bytes``. A backbone with
  held experts also counts its routing, from the pair counts downloaded
  with the logits: ``cascade.routed_pairs`` (every row's tokens, pad rows
  included, × top-k × expert layers), ``cascade.local_pairs`` (the pairs
  the held experts computed) and ``cascade.local_pairs_peak`` (per expert
  layer, the busiest held expert's pairs × the experts held: what a
  grouped matmul padded to its largest group would compute).

* **System accounting.** :meth:`~CascadeService.backbone_cost` reads
  the compiled step's XLA ``cost_analysis()`` (the roofline model's
  source) and :meth:`~CascadeService.system_energy` bills gate duty
  cycle × backbone cost against the always-on backbone
  (:func:`repro.core.energy.cascade_system` /
  :func:`~repro.core.energy.always_on_backbone`);
  :meth:`~CascadeService.roofline` models the per-batch step latency on
  the reference accelerator.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Hashable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import energy
from repro.distributed import roofline as roofline_mod
from repro.launch import steps, telemetry

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CascadeBatch:
    """One collected backbone batch: per-frame logits + provenance.

    Row ``j`` of ``logits`` is the detector output for the frame the
    gate captured at absolute index ``frame_idx[j]`` on sensor
    ``sids[j]``; pad rows are already dropped. Where the host's time
    went for the batch is in :mod:`repro.launch.telemetry`, under its
    ``seq``.
    """
    seq: int
    sids: tuple
    frame_idx: np.ndarray          # (m,) int64 absolute gate indices
    logits: np.ndarray             # (m, n_out) float32
    n_padded: int                  # zero rows the fixed batch carried


@dataclasses.dataclass
class _InFlightBatch:
    seq: int
    logits: Array                  # (batch_size, n_out) device future
    loads: Array                   # (expert layers, held) pair counts
    rows: list                     # [(sid, abs_idx), ...] valid rows


class CascadeService:
    """Batched, double-buffered backbone serving over ``drain_hp`` feeds.

    ``params`` are :func:`repro.launch.steps.init_detector_params`-shaped
    (``{"backbone": ..., "embedder": ...}``) for an **embeds-in** ``cfg``
    (e.g. ``configs.get_smoke("hubert-xlarge")``). ``frame_hw`` must
    match the gate's frames; ``batch_size`` fixes the backbone step
    shape. With a ``mesh`` the backbone params shard across it.

    Feed it either directly (:meth:`submit` takes any ``drain_hp()``
    output) or via :meth:`pump`, which drains every attached sensor of a
    :class:`~repro.launch.serve.FleetService` in place. Results come
    back through :meth:`collect`/:meth:`flush` as
    :class:`CascadeBatch` rows mapped back to (sensor, absolute frame).
    """

    def __init__(self, params, cfg: ModelConfig, *, batch_size: int,
                 frame_hw: tuple[int, int], patch: int = 8,
                 n_out: int = 2, mesh=None, max_inflight: int = 2,
                 j_per_flop: float = energy.EDGE_J_PER_FLOP):
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, "
                             f"got {max_inflight}")
        self.cfg = cfg
        self.batch_size = batch_size
        self.frame_hw = (int(frame_hw[0]), int(frame_hw[1]))
        self.patch = patch
        self.n_out = n_out
        self.max_inflight = max_inflight
        self.j_per_flop = j_per_flop
        self._mesh = mesh
        self._cell = steps.build_detector_cell(
            cfg, batch=batch_size, frame_hw=self.frame_hw, patch=patch,
            n_out=n_out, mesh=mesh)
        if mesh is None:
            self._jit = jax.jit(self._cell.step_fn)
        else:
            self._jit = jax.jit(self._cell.step_fn,
                                in_shardings=self._cell.in_shardings,
                                out_shardings=self._cell.out_shardings)
        if mesh is None:
            self._params = jax.tree.map(jnp.asarray, params)
        else:
            self._params = jax.tree.map(
                jax.device_put, params, self._cell.in_shardings[0])
        self._queue: collections.deque = collections.deque()
        self._pending: collections.deque[_InFlightBatch] = \
            collections.deque()
        self._ready: collections.deque[CascadeBatch] = collections.deque()
        self._compiled = None
        self._seq = 0
        self.frames_in = 0             # frames ever submitted
        self.frames_padded = 0         # zero slack rows ever launched
        self.batches = 0

    # ------------------------------------------------------------------
    # feed
    # ------------------------------------------------------------------

    def submit(self, sid: Hashable, idx, frames) -> int:
        """Enqueue one drain's frames; launches every full batch.

        ``(idx, frames)`` is a ``drain_hp()`` deliverable: ``(M,)``
        absolute indices + ``(M, H, W)`` HP frames — the empty case's
        ``(0, H, W)`` shape contract is exactly what lets a consumer
        like this concatenate drains blindly. Returns frames enqueued.
        """
        # repro-lint: disable=RA003 (admission boundary: ragged drains queue host-side until a full (B, H, W) batch launches)
        idx = np.asarray(idx, np.int64)
        frames = np.asarray(frames, np.float32)  # repro-lint: disable=RA003 (same admission boundary)
        if frames.ndim != 3 or frames.shape[0] != idx.shape[0]:
            raise ValueError(f"drain shapes disagree: idx {idx.shape}, "
                             f"frames {frames.shape}")
        if frames.shape[1:] != self.frame_hw:
            raise ValueError(f"frames are {frames.shape[1:]}, cascade "
                             f"was built for {self.frame_hw}")
        for j in range(idx.shape[0]):
            self._queue.append((sid, int(idx[j]), frames[j]))
        self.frames_in += int(idx.shape[0])
        while len(self._queue) >= self.batch_size:
            self._launch([self._queue.popleft()
                          for _ in range(self.batch_size)])
        return int(idx.shape[0])

    def pump(self, gate) -> int:
        """Drain every attached sensor of ``gate`` (a
        :class:`~repro.launch.serve.FleetService`) into the queue, keyed
        by sensor id; returns frames taken."""
        return sum(self.submit(sid, *gate.drain_hp(sid))
                   for sid in gate.attached)

    # ------------------------------------------------------------------
    # dispatch / collect (PR-8 double-buffering shape)
    # ------------------------------------------------------------------

    def _launch(self, rows: list) -> None:
        B = self.batch_size
        with telemetry.span("cascade.launch", self._seq):
            block = np.zeros((B, *self.frame_hw), np.float32)
            for j, (_, _, frame) in enumerate(rows):
                block[j] = frame
            telemetry.count("cascade.h2d_bytes", block.nbytes)
            dev = (jax.device_put(block) if self._mesh is None
                   else jax.device_put(block, self._cell.in_shardings[1]))
            logits, loads = self._jit(self._params, dev)  # enqueued only
        self._pending.append(_InFlightBatch(
            seq=self._seq, logits=logits, loads=loads,
            rows=[(sid, idx) for sid, idx, _ in rows]))
        self._seq += 1
        self.batches += 1
        self.frames_padded += B - len(rows)
        while len(self._pending) > self.max_inflight:
            self._ready.append(self._finish(self._pending.popleft()))

    def _finish(self, rec: _InFlightBatch) -> CascadeBatch:
        with telemetry.span("cascade.wait", rec.seq):
            # repro-lint: disable=RA003 (designed sync point: blocks on the oldest in-flight batch only)
            logits, loads = jax.device_get((rec.logits, rec.loads))
        if loads.size:
            self._count_routing(loads)
        m = len(rec.rows)
        return CascadeBatch(
            seq=rec.seq,
            sids=tuple(sid for sid, _ in rec.rows),
            frame_idx=np.asarray([i for _, i in rec.rows], np.int64),
            logits=logits[:m],
            n_padded=self.batch_size - m)

    def _count_routing(self, loads: np.ndarray) -> None:
        """Routing counters of one batch from its ``(layers, held)``
        pair counts."""
        cfg = self.cfg
        tokens = self.batch_size * steps.detector_seq_len(self.frame_hw,
                                                          self.patch)
        telemetry.count("cascade.routed_pairs",
                        tokens * cfg.top_k * loads.shape[0])
        telemetry.count("cascade.local_pairs", int(loads.sum()))
        telemetry.count("cascade.local_pairs_peak",
                        int(loads.max(axis=1).sum()) * loads.shape[1])

    def collect(self) -> CascadeBatch | None:
        """Oldest finished batch (FIFO), or None with nothing in flight."""
        if self._ready:
            return self._ready.popleft()
        if not self._pending:
            return None
        return self._finish(self._pending.popleft())

    def flush(self) -> list[CascadeBatch]:
        """Force the partial tail batch out and drain the pipeline."""
        if self._queue:
            self._launch([self._queue.popleft()
                          for _ in range(len(self._queue))])
        out = list(self._ready)
        self._ready.clear()
        while self._pending:
            out.append(self._finish(self._pending.popleft()))
        return out

    @property
    def queued(self) -> int:
        """Frames waiting for a full batch (flush() forces them)."""
        return len(self._queue)

    def compile_count(self) -> int:
        """XLA compilations of the backbone step — the ragged-drain
        no-retrace witness (must freeze after the first batch)."""
        return self._jit._cache_size()

    # ------------------------------------------------------------------
    # reference + accounting
    # ------------------------------------------------------------------

    def eager(self, frames) -> np.ndarray:
        """Per-frame reference evaluation: one step call per frame.

        Runs each ``(H, W)`` frame alone (row 0 of a zero-padded batch)
        through the SAME jitted step and returns ``(M, n_out)`` logits.
        Without experts the cascade's batched outputs are bitwise-equal
        to this — the ``lax.map`` row independence makes it so by
        construction; with experts they agree up to the rounding of
        batched products.
        """
        frames = np.asarray(frames, np.float32)
        out = np.empty((frames.shape[0], self.n_out), np.float32)
        block = np.zeros((self.batch_size, *self.frame_hw), np.float32)
        for j in range(frames.shape[0]):
            block[0] = frames[j]
            dev = (jax.device_put(block) if self._mesh is None
                   else jax.device_put(block, self._cell.in_shardings[1]))
            out[j] = np.asarray(self._jit(self._params, dev)[0])[0]
        return out

    def _ensure_compiled(self):
        if self._compiled is None:
            abs_p, abs_f = self._cell.abstract_args
            self._compiled = self._jit.lower(abs_p, abs_f).compile()
        return self._compiled

    def backbone_cost(self) -> energy.BackboneCost:
        """Measured per-frame FLOPs/bytes/Joules of the compiled step."""
        return energy.backbone_cost(self._ensure_compiled(),
                                    self.batch_size,
                                    j_per_flop=self.j_per_flop)

    def roofline(self) -> roofline_mod.Roofline:
        """Roofline latency model of one backbone batch on the
        reference accelerator (the per-batch service step the gate's
        duty cycle amortizes)."""
        seq = steps.detector_seq_len(self.frame_hw, self.patch)
        shape = ShapeConfig(name=f"detector_b{self.batch_size}",
                            seq_len=seq, global_batch=self.batch_size,
                            kind="prefill")
        chips = self._mesh.size if self._mesh is not None else 1
        mesh_name = ("x".join(str(s) for s in
                              self._mesh.devices.shape)
                     if self._mesh is not None else "single")
        return roofline_mod.from_compiled(
            self._ensure_compiled(), arch=self.cfg.arch_id, shape=shape,
            mesh_name=mesh_name, chips=chips)

    def system_energy(self, log, params: energy.EnergyParams | None = None,
                      precision: str = "float32"
                      ) -> dict[str, energy.EnergyBreakdown]:
        """Per-frame system energy: this cascade vs the always-on backbone.

        ``log`` is the gate's :class:`~repro.core.sensor_control.
        CaptureLog` (closed loop — a real ``hp_bits`` is required);
        ``"cascade"`` bills LP sampling + HDC + duty-cycled HP capture +
        duty × measured backbone cost, ``"always_on"`` bills HP capture
        + backbone on every frame.
        """
        cost = self.backbone_cost()
        return {"cascade": energy.cascade_system(log, cost, params,
                                                 precision),
                "always_on": energy.always_on_backbone(cost, params)}
