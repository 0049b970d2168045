"""Streaming scorer throughput: batched kernel vs sequential vs jnp-vmap.

The tentpole measurement for the batched streaming pipeline: frames/sec of
the HyperSense frame-scoring hot path (fragment score map ->
frame_detection_score) under three execution strategies:

* ``jnp-vmap``     — pure-jnp scoring vmapped over the chunk
* ``seq-kernel``   — the sliding-scores kernel, one launch PER FRAME
  (the pre-batching hot path: O(N) dispatches)
* ``batch-kernel`` — ONE launch per chunk, grid ``(N, n_dt)``,
  sharing a single ScoreTiles precompute

On CPU the kernel paths run in Pallas interpret mode, so absolute numbers
are small; the *ratio* batch-kernel/seq-kernel is the claim being checked
(one launch amortizes dispatch + norms + epilogue over the chunk). On TPU
the same code compiles and the gap widens.

Run:  PYTHONPATH=src python benchmarks/stream_throughput.py [--frames 32]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import hypersense
from repro.core.encoding import make_perm_base_rows
from repro.kernels import ops

# CPU-tractable scale (interpret mode executes grid steps in Python).
FRAME = 32
FRAG = 8
STRIDE = 4
DIM = 256
BLOCK_D = 128
REPS = 3


def _make_model(dim: int, frag: int, stride: int):
    B0, b = make_perm_base_rows(jax.random.PRNGKey(0), frag, dim)
    C = jax.random.normal(jax.random.PRNGKey(1), (2, dim))
    return hypersense.HyperSenseModel(C, B0, b, frag, frag, stride,
                                      t_score=0.0, t_detection=2)


def _time(fn, reps: int = REPS) -> float:
    """Best-of-N wall time: min suppresses scheduler noise on shared CPUs."""
    fn()  # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(n_frames: int = FRAME, frame: int = FRAME, frag: int = FRAG,
        stride: int = STRIDE, dim: int = DIM, reps: int = REPS):
    model = _make_model(dim, frag, stride)
    frames = jax.random.uniform(jax.random.PRNGKey(2),
                                (n_frames, frame, frame))
    tiles = ops.precompute_tiles(model.B0, model.b, model.class_hvs,
                                 W=frame, w=frag, stride=stride,
                                 block_d=BLOCK_D)

    def jnp_vmap():
        jax.block_until_ready(
            hypersense.frame_scores_batch(model, frames, backend="jnp"))

    def seq_kernel():
        for i in range(n_frames):
            s = ops.fragment_score_map(
                frames[i], model.class_hvs, model.B0, model.b, h=frag,
                w=frag, stride=stride, tiles=tiles)
            jax.block_until_ready(
                hypersense.frame_detection_score(s, model.t_detection))

    def batch_kernel():
        jax.block_until_ready(
            hypersense.frame_scores_batch(model, frames, backend="pallas",
                                          tiles=tiles))

    rows = []
    fps = {}
    for name, fn in [("jnp-vmap", jnp_vmap), ("seq-kernel", seq_kernel),
                     ("batch-kernel", batch_kernel)]:
        dt = _time(fn, reps)
        fps[name] = n_frames / dt
        rows.append({"name": f"stream_throughput/{name}",
                     "frames_per_sec": f"{fps[name]:.1f}",
                     "ms_per_chunk": f"{dt * 1e3:.1f}",
                     "batch": n_frames})
    rows.append({"name": "stream_throughput/batch_vs_seq_speedup",
                 "value": f"{fps['batch-kernel'] / fps['seq-kernel']:.2f}x",
                 "batch": n_frames})
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=FRAME,
                    help="chunk size (batch of frames per step)")
    ap.add_argument("--frame-size", type=int, default=FRAME)
    ap.add_argument("--frag", type=int, default=FRAG)
    ap.add_argument("--stride", type=int, default=STRIDE)
    ap.add_argument("--dim", type=int, default=DIM)
    ap.add_argument("--reps", type=int, default=REPS)
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless batch-kernel >= seq-kernel "
                         "frames/sec (the batching claim; use batch >= 8)")
    args = ap.parse_args()
    rows = run(args.frames, args.frame_size, args.frag, args.stride,
               args.dim, args.reps)
    fps = {}
    for row in rows:
        name = row.pop("name")
        if "frames_per_sec" in row:
            fps[name.split("/")[-1]] = float(row["frames_per_sec"])
        print(name + "," + ",".join(f"{k}={v}" for k, v in row.items()))
    if args.check and fps["batch-kernel"] < fps["seq-kernel"]:
        raise SystemExit(
            f"REGRESSION: batch-kernel {fps['batch-kernel']:.1f} fps < "
            f"seq-kernel {fps['seq-kernel']:.1f} fps at batch "
            f"{args.frames}")


if __name__ == "__main__":
    main()
