"""The fleet step builder (paper §I: escalating sensor counts).

HyperSense's always-on HDC front-end is fleet-scale in deployment — one
edge site aggregates many radar/camera feeds (cf. Eggimann et al.'s
always-on SCM accelerator, HyperCam's camera fleets). This module
multiplies the chunked gate step (:mod:`repro.sensing.stream`) along a
sensor axis without multiplying kernel launches; its one caller is
:class:`repro.launch.serve.FleetService`:

* ``(S, C, H, W)`` **super-chunks** — S concurrent streams, C frames each —
  are flattened to an ``S*C`` batch and scored by ONE ``pallas_call``
  (grid ``(S*C, n_dt)``) against one shared
  :class:`~repro.kernels.sliding_scores.ScoreTiles` precompute
  (:func:`repro.kernels.ops.fragment_score_map_fleet`);
* per-stream controller hysteresis is ``vmap(gate_scan)`` — S independent
  ``lax.scan`` hold states carried across super-chunks, so every stream
  sees exactly the gating it would get alone;
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
  the unsharded step; without a mesh the exact same code runs
  unsharded.

:func:`fleet_report` turns the per-stream gate decisions into per-stream
:class:`~repro.core.sensor_control.StreamStats` plus a fleet-aggregate
energy account built on :mod:`repro.core.energy`.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import energy
from repro.core.sensor_control import (CaptureLog, StreamStats,
                                       stats_from_batch)
from repro.distributed import sharding as shlib
from repro.sensing import adc as adc_sim
from repro.sensing.stream import (StreamState, super_chunk_fn,
                                  super_chunk_step)


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


def _build_step(mesh, axes, hd_axes, tiles_spec, **static):
    """Fleet step callable: the shared module-level jit, or shard_map'd.

    Unsharded, this is just :func:`repro.sensing.stream.super_chunk_step`
    with the static config bound — every service shares its global trace
    cache. Either way the carried ``StreamState`` pytree is donated to
    XLA, so a service that steps forever rolls one state allocation
    instead of reallocating per chunk — callers must never re-read a
    donated input after the call.
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
        return functools.partial(super_chunk_step, **static)
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
        check_vma=False), donate_argnums=(1,))


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

    With a ``capture`` log (``repro.launch.replay.FleetRunner`` stacks
    the service's per-sensor logs) the fleet is billed from what the ADCs
    *actually* converted and transmitted
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
