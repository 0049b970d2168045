"""Fleet scoring throughput: batched super-chunks vs a loop of runners.

The tentpole measurement for the multi-sensor runtime: total frames/sec of
S concurrent sensor streams under two execution strategies:

* ``looped-runners`` — a Python loop over S independent ``StreamRunner``
  instances, i.e. S jitted steps (S kernel launches on the ``pallas``
  backend) per chunk interval — the pre-fleet way to serve S sensors;
* ``fleet-batched``  — one ``FleetRunner`` consuming ``(S, C, H, W)``
  super-chunks: the S*C axis is flattened into a single kernel grid, ONE
  launch per super-chunk, one shared ScoreTiles precompute, and one
  vmapped ``gate_scan`` carrying all S hold states.

Both paths produce identical per-stream results (tests/test_fleet.py);
this benchmark measures only the dispatch/batching win. On CPU the pallas
paths run in interpret mode, so absolute numbers are small; the *ratio*
fleet/looped is the claim being checked (``--check`` enforces it at
S >= 4). On TPU the same code compiles and the gap widens.

Run:  PYTHONPATH=src python benchmarks/fleet_throughput.py [--sensors 4]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

if "--mesh" in sys.argv:
    # the mesh sweep needs the forced-8-device host platform, and the
    # flag only takes effect before jax initializes — self-serve it so
    # `python benchmarks/fleet_throughput.py --mesh` works standalone
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

from repro.core import hypersense
from repro.core.encoding import make_perm_base_rows
from repro.core.sensor_control import ControllerConfig
from repro.launch.mesh import make_mesh
from repro.sensing.fleet import FleetRunner
from repro.sensing.stream import StreamRunner

# CPU-tractable scale (interpret mode executes grid steps in Python).
SENSORS = 4
FRAMES = 16          # per stream, per timed pass
CHUNK = 4            # small chunks -> more launches -> the amortization
                     # (the thing being measured) dominates the pass
FRAME = 32
FRAG = 8
STRIDE = 8           # small (my, n_dt) grid keeps per-launch work low, so
DIM = 256            # the S-fold launch fan-in is what gets measured
BLOCK_D = 256
REPS = 3


def _make_model(dim: int, frag: int, stride: int):
    B0, b = make_perm_base_rows(jax.random.PRNGKey(0), frag, dim)
    C = jax.random.normal(jax.random.PRNGKey(1), (2, dim))
    return hypersense.HyperSenseModel(C, B0, b, frag, frag, stride,
                                      t_score=0.0, t_detection=2)


def _time(fn, reps: int = REPS) -> float:
    """Best-of-N wall time: min suppresses scheduler noise on shared CPUs."""
    fn()  # warmup: jit compile + tiles precompute
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(sensors: int = SENSORS, n_frames: int = FRAMES, chunk: int = CHUNK,
        frame: int = FRAME, frag: int = FRAG, stride: int = STRIDE,
        dim: int = DIM, backend: str = "pallas", reps: int = REPS):
    model = _make_model(dim, frag, stride)
    config = ControllerConfig(hold_frames=3)
    frames = jax.random.uniform(jax.random.PRNGKey(2),
                                (sensors, n_frames, frame, frame))
    total = sensors * n_frames

    runners = [StreamRunner(model, config, chunk_size=chunk,
                            backend=backend, block_d=BLOCK_D)
               for _ in range(sensors)]
    fleet = FleetRunner(model, config, chunk_size=chunk, backend=backend,
                        block_d=BLOCK_D)

    def looped():
        for s, r in enumerate(runners):
            r.process(frames[s])

    def batched():
        fleet.process(frames)

    rows = []
    fps = {}
    for name, fn in [("looped-runners", looped),
                     ("fleet-batched", batched)]:
        dt = _time(fn, reps)
        fps[name] = total / dt
        rows.append({"name": f"fleet_throughput/{name}",
                     "frames_per_sec": f"{fps[name]:.1f}",
                     "ms_per_pass": f"{dt * 1e3:.1f}",
                     "sensors": sensors, "backend": backend})
    rows.append({"name": "fleet_throughput/fleet_vs_looped_speedup",
                 "value": f"{fps['fleet-batched'] / fps['looped-runners']:.2f}x",
                 "sensors": sensors, "backend": backend})
    return rows


# --- 2-D mesh sweep ---------------------------------------------------------
# Scale the fleet along BOTH logical axes on the forced-8-device host
# mesh: the sensor axis to S=1024 streams (8x1 mesh), and the hyperdim
# axis to D=16384 (1x8 mesh) — a config the VMEM byte model certifies
# cannot run single-slab on one device, but whose 8-way D-shard fits.
MESH_SWEEP_S = (8, 64, 256, 1024)
MESH_FRAME = 16       # small frames keep the S=1024 jnp-oracle pass in RAM
MESH_CHUNK = 2
MESH_FRAMES = 2       # per stream, per timed pass
MESH_BIG_DIM = 16384
MESH_BIG_BLOCK_D = 2048    # 8-way D-shard: one 2048-wide tile per device
MESH_BIG_FRAME = 64
MESH_BIG_S = 4


def run_mesh(reps: int = REPS, check: bool = False):
    import numpy as np

    from repro.distributed import sharding as shlib
    from repro.kernels.sliding_scores_int import int_datapath_bounds

    if jax.device_count() < 8:
        raise SystemExit(
            f"--mesh needs 8 devices, got {jax.device_count()} — the "
            "self-set XLA_FLAGS came too late (jax already initialized?)")

    rows = []
    model = _make_model(DIM, FRAG, STRIDE)
    config = ControllerConfig(hold_frames=3)

    def make_fleet():
        # jnp backend + int8: the tiled-oracle path every host serves the
        # int datapath from — and the fastest way to reach S=1024 on CPU
        return FleetRunner(model, config, chunk_size=MESH_CHUNK,
                           backend="jnp", block_d=BLOCK_D, adc_bits=8,
                           precision="int8")

    # sensor-axis sweep on the 8x1 mesh
    mesh_s = make_mesh((8, 1), ("data", "model"))
    for S in MESH_SWEEP_S:
        frames = jax.random.uniform(jax.random.PRNGKey(2),
                                    (S, MESH_FRAMES, MESH_FRAME,
                                     MESH_FRAME))
        fleet = make_fleet()
        with shlib.use_mesh(mesh_s):
            dt = _time(lambda: fleet.process(frames), reps)
        rows.append({"name": f"fleet_throughput/mesh_8x1_S{S}",
                     "frames_per_sec": f"{S * MESH_FRAMES / dt:.1f}",
                     "ms_per_pass": f"{dt * 1e3:.1f}",
                     "sensors": S, "mesh": "8x1"})

    # parity gate: the sharded sweep config is BITWISE the unsharded one
    frames = jax.random.uniform(jax.random.PRNGKey(2),
                                (8, MESH_FRAMES, MESH_FRAME, MESH_FRAME))
    with shlib.use_mesh(mesh_s):
        got = make_fleet().process(frames)
    want = make_fleet().process(frames)
    bitwise = all(np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(got, want))
    rows.append({"name": "fleet_throughput/mesh_parity_bitwise",
                 "value": str(bitwise).lower(), "mesh": "8x1"})
    if check and not bitwise:
        raise SystemExit("REGRESSION: 8x1-mesh fleet outputs differ from "
                         "the unsharded runner")

    # hyperdim-axis scale-out: D=16384 on the 1x8 mesh. One device would
    # need the whole hypervector resident per grid step (block_d = D) —
    # the byte model rejects that working set; the 8-way D-shard's
    # per-device 2048-wide tile fits with room to spare.
    single = int_datapath_bounds(8, MESH_BIG_FRAME, MESH_BIG_FRAME,
                                 FRAG, FRAG, stride=STRIDE,
                                 block_d=MESH_BIG_DIM)
    shard = int_datapath_bounds(8, MESH_BIG_FRAME, MESH_BIG_FRAME,
                                FRAG, FRAG, stride=STRIDE,
                                block_d=MESH_BIG_BLOCK_D)
    rows.append({"name": "fleet_throughput/mesh_1x8_D16384_vmem",
                 "single_device_bytes": single["vmem_bytes"],
                 "single_device_fits": str(single["fits"]).lower(),
                 "sharded_bytes": shard["vmem_bytes"],
                 "sharded_fits": str(shard["fits"]).lower(),
                 "limit_bytes": single["vmem_limit_bytes"]})
    if check and (single["fits"] or not shard["fits"]):
        raise SystemExit(
            "REGRESSION: VMEM byte model no longer certifies the D=16384 "
            f"scale-out (single fits={single['fits']}, "
            f"shard fits={shard['fits']})")

    big_model = _make_model(MESH_BIG_DIM, FRAG, STRIDE)
    big = FleetRunner(big_model, config, chunk_size=MESH_CHUNK,
                      backend="jnp", block_d=MESH_BIG_BLOCK_D, adc_bits=8,
                      precision="int8")
    frames = jax.random.uniform(jax.random.PRNGKey(3),
                                (MESH_BIG_S, MESH_FRAMES, MESH_BIG_FRAME,
                                 MESH_BIG_FRAME))
    with shlib.use_mesh(make_mesh((1, 8), ("data", "model"))):
        dt = _time(lambda: big.process(frames), reps)
        assert big._step_key[2] == ("model",), \
            "D=16384 fleet did not shard the hyperdim axis"
    rows.append({"name": "fleet_throughput/mesh_1x8_D16384",
                 "frames_per_sec": f"{MESH_BIG_S * MESH_FRAMES / dt:.1f}",
                 "ms_per_pass": f"{dt * 1e3:.1f}",
                 "dim": MESH_BIG_DIM, "mesh": "1x8"})
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sensors", type=int, default=SENSORS,
                    help="number of concurrent sensor streams S")
    ap.add_argument("--frames", type=int, default=FRAMES,
                    help="frames per stream per timed pass")
    ap.add_argument("--chunk", type=int, default=CHUNK)
    ap.add_argument("--frame-size", type=int, default=FRAME)
    ap.add_argument("--frag", type=int, default=FRAG)
    ap.add_argument("--stride", type=int, default=STRIDE)
    ap.add_argument("--dim", type=int, default=DIM)
    ap.add_argument("--backend", default="pallas",
                    choices=["pallas", "jnp"])
    ap.add_argument("--reps", type=int, default=REPS)
    ap.add_argument("--mesh", action="store_true",
                    help="run the 2-D mesh sweep instead: sensor axis to "
                         "S=1024 (8x1) and hyperdim axis to D=16384 "
                         "(1x8) on a forced-8-device host mesh; --check "
                         "gates bitwise parity + the VMEM certification")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless fleet-batched >= "
                         "looped-runners frames/sec (the fleet batching "
                         "claim; use --sensors >= 4). With --mesh: gate "
                         "mesh parity and the D=16384 VMEM certification")
    try:
        from benchmarks import common   # -m benchmarks.run / repo root
    except ImportError:
        import common                   # standalone: script dir on path
    common.add_json_arg(ap)
    args = ap.parse_args()
    if args.mesh:
        rows = run_mesh(args.reps, check=args.check)
        if args.json:
            print("json ->", common.write_json(args.json,
                                               "fleet_throughput_mesh",
                                               rows))
        for row in rows:
            name = row.pop("name")
            print(name + "," + ",".join(f"{k}={v}"
                                        for k, v in row.items()))
        return
    rows = run(args.sensors, args.frames, args.chunk, args.frame_size,
               args.frag, args.stride, args.dim, args.backend, args.reps)
    if args.json:
        print("json ->", common.write_json(args.json, "fleet_throughput",
                                           rows))
    fps = {}
    for row in rows:
        name = row.pop("name")
        if "frames_per_sec" in row:
            fps[name.split("/")[-1]] = float(row["frames_per_sec"])
        print(name + "," + ",".join(f"{k}={v}" for k, v in row.items()))
    if args.check and fps["fleet-batched"] < fps["looped-runners"]:
        raise SystemExit(
            f"REGRESSION: fleet-batched {fps['fleet-batched']:.1f} fps < "
            f"looped-runners {fps['looped-runners']:.1f} fps at "
            f"S={args.sensors}")


if __name__ == "__main__":
    main()
