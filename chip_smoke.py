#!/usr/bin/env python3
"""Drive the served gate -> cascade path once on a TPU and check it.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py             # phases a-c, one chip
    python3 chip_smoke.py --chips 4   # the sharded fleet only, four chips

Phases a-c run at the paper's operating point (``configs/hypersense.py``:
128x128 frames, 96x96 fragments, stride 8, D=5000, 4-bit LP and 12-bit HP
ADC), through the entry points a deployment calls:

a. gate, float32 — train the Fragment gate from a seed, serve 16 sensors
   through ``FleetService(backend="pallas")`` for one warm-up tick and four
   ticks, and compare every score with ``backend="jnp"`` on the same
   arrivals (its matmuls at "highest" precision);
b. gate, int8 — the same on the integer ADC-code datapath (8-bit codes),
   against the int path's jnp twin;
c. cascade — phase a's high-precision captures go through
   ``CascadeService`` with hubert-xlarge at its published width, compared
   with per-frame eager evaluation.

``--chips 4`` runs only the mesh phase: ``FleetService`` under a 4x1
(sensors) mesh at the paper's point, and under a 2x2 (sensors x hyperdim)
mesh at D=16384, each against the one-device run of the same arrivals.

Each phase prints one line: shapes, compile and wall seconds, and its
largest deviation from the reference. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits 1 before running any phase. The
persistent compilation cache follows ``JAX_COMPILATION_CACHE_DIR`` when it
is set and ``<checkout>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import hubert_xlarge, hypersense as hs_config  # noqa: E402
from repro.core import encoding, fragment_model as fm  # noqa: E402
from repro.core import hypersense, metrics  # noqa: E402
from repro.core.sensor_control import (CaptureConfig,  # noqa: E402
                                       ControllerConfig)
from repro.distributed import sharding as shlib  # noqa: E402
from repro.kernels.sliding_scores import tile_layout  # noqa: E402
from repro.launch import steps  # noqa: E402
from repro.launch.cascade import CascadeService  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import FleetService  # noqa: E402
from repro.sensing import adc, fragments, synthetic  # noqa: E402

#: largest |score - reference| admitted. Scores are cosine differences in
#: [-2, 2]; a gate decision may differ from the reference's only where
#: the reference score lies within this distance of t_score.
SCORE_TOL = 2e-4

#: 10 Hz idle trickle, 60 Hz bursts held 6 frames (the closed loop of
#: examples/intelligent_sensing_e2e.py --control)
RATES = ControllerConfig(base_rate_hz=10.0, active_rate_hz=60.0,
                         hold_frames=6)

#: hypervector width of the hyperdim-sharded mesh case (README: the size
#: that cannot sit in one device's slab)
MESH_DIM = 16384


@dataclasses.dataclass
class Served:
    """One service's outputs over the run, ``(ticks, S, C)`` each."""
    svc: FleetService
    scores: np.ndarray
    fired: np.ndarray
    gated: np.ndarray
    warm_s: float          # first tick: compile + run
    wall_s: float          # the remaining ticks, pipelined


def train_gate(cfg: hs_config.HyperSenseConfig, seed: int
               ) -> hypersense.HyperSenseModel:
    """Fragment gate at ``cfg``'s geometry on LP captures, thresholded at
    FPR 0.1 (the recipe of examples/intelligent_sensing_e2e.py)."""
    rc = synthetic.RadarConfig(height=cfg.frame_h, width=cfg.frame_w)
    frames, masks, _ = synthetic.make_dataset(jax.random.PRNGKey(seed), 60,
                                              rc)
    lp = adc.quantize(frames, cfg.adc_low_bits)
    frs, labs = fragments.sample_fragments(
        np.asarray(lp), np.asarray(masks), h=cfg.fragment, w=cfg.fragment,
        per_frame=2, seed=seed)
    model, _ = fm.train_fragment_model(
        jax.random.PRNGKey(seed + 1), jnp.asarray(frs), jnp.asarray(labs),
        dim=cfg.dim, epochs=10)
    B0 = model.B.reshape(cfg.fragment, cfg.fragment, -1)[:, 0, :]
    hs = hypersense.from_fragment_model(model, B0, h=cfg.fragment,
                                        w=cfg.fragment, stride=cfg.stride)
    te, _, te_labels = synthetic.make_dataset(jax.random.PRNGKey(seed + 2),
                                              24, rc)
    scores = np.asarray(hypersense.frame_scores_batch(
        hs, adc.quantize(te, cfg.adc_low_bits), 0, backend="pallas"))
    fpr, tpr, thr = metrics.roc_curve(scores, np.asarray(te_labels))
    return hs._replace(t_score=float(metrics.threshold_at_fpr(fpr, tpr, thr,
                                                              0.1)))


def random_gate(cfg: hs_config.HyperSenseConfig, dim: int, seed: int
                ) -> hypersense.HyperSenseModel:
    """Untrained gate of width ``dim`` (weights from ``seed``): enough
    for a parity check, which compares two runs of the same model."""
    B0, b = encoding.make_perm_base_rows(jax.random.PRNGKey(seed),
                                         cfg.fragment, dim)
    chvs = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, dim))
    return hypersense.HyperSenseModel(chvs, B0, b, cfg.fragment,
                                      cfg.fragment, cfg.stride,
                                      t_score=0.0, t_detection=0)


def make_streams(cfg: hs_config.HyperSenseConfig, n_sensors: int,
                 n_frames: int, seed: int) -> np.ndarray:
    """``(S, n_frames, H, W)`` float32 host frames, one synthetic radar
    stream with object tracks per sensor."""
    rc = synthetic.RadarConfig(height=cfg.frame_h, width=cfg.frame_w)
    key = jax.random.PRNGKey(seed)
    return np.stack([np.asarray(synthetic.make_stream(
        jax.random.fold_in(key, s), n_frames, rc, event_prob=0.05,
        event_len=10)[0], np.float32) for s in range(n_sensors)])


def serve(model, streams: np.ndarray, *, chunk: int, backend: str,
          precision: str, adc_bits: int, block_d: int = 512) -> Served:
    """Serve ``streams`` tick by tick through a fresh ``FleetService``
    (under the active mesh, if any): one warm-up tick, then the rest
    dispatched back to back and flushed."""
    S, n = streams.shape[:2]
    svc = FleetService(model, RATES, n_slots=S, chunk_size=chunk,
                       backend=backend, precision=precision,
                       adc_bits=adc_bits, block_d=block_d,
                       control=CaptureConfig(hp_bits=12))
    for sid in range(S):
        svc.attach(sid)
    tick = lambda t: {sid: streams[sid, t * chunk:(t + 1) * chunk]
                      for sid in range(S)}
    t0 = time.perf_counter()
    svc.dispatch(tick(0))
    done = svc.flush()
    t1 = time.perf_counter()
    for t in range(1, n // chunk):
        svc.dispatch(tick(t))
    done += svc.flush()
    t2 = time.perf_counter()
    out = [np.stack([[d.outputs[sid][k] for sid in range(S)] for d in done])
           for k in range(3)]
    return Served(svc, *out, warm_s=t1 - t0, wall_s=t2 - t1)


def compare(got: Served, want: Served, t_score: float) -> dict:
    """Score deviation and decision agreement of two runs; raises when
    they disagree beyond :data:`SCORE_TOL`.

    A decision may flip only where the reference score lies within the
    tolerance of ``t_score``; such a flip changes what that sensor's
    closed loop samples next, so the sensor's later decisions are not
    compared.
    """
    dev = float(np.abs(got.scores - want.scores).max())
    if not np.isfinite(got.scores).all() or dev > SCORE_TOL:
        raise AssertionError(f"max |score - reference| = {dev:.3g} > "
                             f"{SCORE_TOL:g}")
    fired_g = got.fired.transpose(1, 0, 2).reshape(got.fired.shape[1], -1)
    fired_w = want.fired.transpose(1, 0, 2).reshape(fired_g.shape)
    near = np.abs(want.scores - t_score).transpose(1, 0, 2).reshape(
        fired_g.shape) <= SCORE_TOL
    compared = 0
    for s in range(fired_g.shape[0]):
        diff = np.flatnonzero(fired_g[s] != fired_w[s])
        if diff.size and not near[s, diff[0]]:
            raise AssertionError(f"sensor {s}: gate decision differs at "
                                 f"frame {diff[0]}, away from t_score")
        compared += diff[0] if diff.size else fired_g.shape[1]
    return {"dev": dev, "decisions": int(compared),
            "frames": int(fired_g.size)}


def gate_phase(model, streams: np.ndarray, *, precision: str, adc_bits: int,
               chunk: int) -> tuple[FleetService, dict]:
    """Phases a/b: the pallas-served gate against the jnp backend on the
    same arrivals. Returns the pallas service (its HP captures feed the
    cascade) and the phase's numbers."""
    got = serve(model, streams, chunk=chunk, backend="pallas",
                precision=precision, adc_bits=adc_bits)
    with jax.default_matmul_precision("highest"):
        want = serve(model, streams, chunk=chunk, backend="jnp",
                     precision=precision, adc_bits=adc_bits)
    res = compare(got, want, model.t_score)
    res.update(warm_s=got.warm_s, wall_s=got.wall_s,
               ticks=got.scores.shape[0],
               duty=float(got.gated.mean()),
               kernel="tpu_custom_call" in got.svc.compiled_step_text())
    return got.svc, res


def cascade_phase(svc: FleetService, mcfg, *, frame_hw: tuple[int, int],
                  patch: int, batch: int, seed: int,
                  max_eager: int = 24) -> dict:
    """Phase c: the gate's HP drains through the batched detector backbone,
    bitwise against per-frame eager evaluation of the first
    ``max_eager`` frames; exactly one backbone compile."""
    params = steps.init_detector_params(jax.random.PRNGKey(seed), mcfg,
                                        frame_hw=frame_hw, patch=patch)
    casc = CascadeService(params, mcfg, batch_size=batch,
                          frame_hw=frame_hw, patch=patch)
    drains = {sid: svc.drain_hp(sid) for sid in svc.attached}
    frames = np.concatenate([fr for _, fr in drains.values()])
    if not len(frames):
        raise AssertionError("the gate captured no high-precision frames")
    t0 = time.perf_counter()
    for sid, (idx, fr) in drains.items():
        casc.submit(sid, idx, fr)
    batches = casc.flush()
    wall = time.perf_counter() - t0
    logits = np.concatenate([b.logits for b in batches])
    if logits.shape != (len(frames), casc.n_out) \
            or not np.isfinite(logits).all():
        raise AssertionError(f"cascade returned {logits.shape} logits for "
                             f"{len(frames)} frames (or non-finite)")
    k = min(len(frames), max_eager)
    dev = float(np.abs(logits[:k] - casc.eager(frames[:k])).max())
    if dev != 0.0:
        raise AssertionError(f"batched logits differ from eager by {dev:g}")
    if casc.compile_count() != 1:
        raise AssertionError(f"backbone compiled {casc.compile_count()} "
                             f"times, expected once")
    return {"frames": len(frames), "batches": len(batches), "wall_s": wall,
            "eager_checked": k, "dev": dev}


def mesh_phase(model, streams: np.ndarray, mesh_shape: tuple[int, int], *,
               chunk: int, block_d: int) -> dict:
    """The sharded fleet: ``FleetService`` under a (sensors, hyperdim)
    mesh against the one-device run of the same arrivals."""
    one = serve(model, streams, chunk=chunk, backend="pallas",
                precision="float32", adc_bits=4, block_d=block_d)
    mesh = make_mesh(mesh_shape, ("data", "model"))
    with shlib.use_mesh(mesh):
        many = serve(model, streams, chunk=chunk, backend="pallas",
                     precision="float32", adc_bits=4, block_d=block_d)
        kernel = "tpu_custom_call" in many.svc.compiled_step_text()
    # the shards the fleet's logical-axis rules give each axis
    n_dt = tile_layout(model.class_hvs.shape[-1], block_d)[1]
    hd = shlib.spec_for((n_dt,), ("hyperdim",), mesh)[0]
    hd = () if hd is None else (hd,) if isinstance(hd, str) else hd
    res = compare(many, one, model.t_score)
    res.update(warm_s=many.warm_s, wall_s=many.wall_s,
               bitwise=bool((many.scores == one.scores).all()),
               sensor_shards=shlib.mesh_extent("sensors", mesh)[1],
               hyperdim_shards=int(np.prod([mesh.shape[a] for a in hd])),
               kernel=kernel)
    return res


def _line(name: str, shapes: str, res: dict) -> None:
    print(f"{name}: {shapes} | " + ", ".join(
        f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in res.items()), flush=True)


def _require_kernel(name: str, res: dict) -> None:
    if not res["kernel"]:
        raise AssertionError(f"{name}: the served step holds no compiled "
                             f"Pallas kernel (tpu_custom_call)")


def run_one_chip(seed: int) -> None:
    cfg = hs_config.config()
    S, C, ticks = 16, 32, 5
    geo = (f"S={S} C={C} frames={cfg.frame_h}x{cfg.frame_w} "
           f"frag={cfg.fragment} stride={cfg.stride} D={cfg.dim}")
    t0 = time.perf_counter()
    model = train_gate(cfg, seed)
    streams = make_streams(cfg, S, ticks * C, seed + 10)
    print(f"setup: trained gate D={cfg.dim} t_score={model.t_score:.6g}, "
          f"streams {streams.shape} in {time.perf_counter() - t0:.1f}s",
          flush=True)

    svc, res = gate_phase(model, streams, precision="float32",
                          adc_bits=cfg.adc_low_bits, chunk=C)
    _line("phase a gate float32", geo + f" adc={cfg.adc_low_bits}b", res)
    _require_kernel("phase a", res)

    _, res = gate_phase(model, streams, precision="int8", adc_bits=8,
                        chunk=C)
    _line("phase b gate int8", geo + " adc=8b", res)
    _require_kernel("phase b", res)

    mcfg = hubert_xlarge.config()
    res = cascade_phase(svc, mcfg, frame_hw=(cfg.frame_h, cfg.frame_w),
                        patch=8, batch=8, seed=seed + 20)
    _line("phase c cascade", f"{mcfg.arch_id} L={mcfg.n_layers} "
          f"d={mcfg.d_model} heads={mcfg.n_heads} d_ff={mcfg.d_ff} "
          f"batch=8 patch=8", res)


def run_four_chips(seed: int) -> None:
    cfg = hs_config.config()
    S, C, ticks = 16, 32, 5
    model = train_gate(cfg, seed)
    streams = make_streams(cfg, S, ticks * C, seed + 10)
    res = mesh_phase(model, streams, (4, 1), chunk=C, block_d=512)
    _line("mesh 4x1 sensors", f"S={S} C={C} D={cfg.dim}", res)
    _require_kernel("mesh 4x1", res)
    if res["sensor_shards"] != 4:
        raise AssertionError("mesh 4x1: sensors did not shard four ways")
    wide = random_gate(cfg, MESH_DIM, seed + 30)
    res = mesh_phase(wide, streams, (2, 2), chunk=C, block_d=512)
    _line("mesh 2x2 sensors x hyperdim", f"S={S} C={C} D={MESH_DIM} "
          f"block_d=512", res)
    _require_kernel("mesh 2x2", res)
    if (res["sensor_shards"], res["hyperdim_shards"]) != (2, 2):
        raise AssertionError("mesh 2x2: sensors and hyperdim did not both "
                             "shard two ways")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-fleet phase, on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {backend!r}; no phase "
              f"was run", file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        run_four_chips(args.seed)
    else:
        run_one_chip(args.seed)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devices)}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
