#!/usr/bin/env python3
"""Sweep the sensor count of an open-loop cell, to find the knee.

    python3 bench/sweep.py --workload radar-f32.open60 --seconds 15 \
        --sensors 26 30 34 38 --seed 1

For each sensor count, in one process: set up the cell as a run does with
that many sensors, serve its open-loop schedule for ``--seconds``, and
print one JSON line: the decision latency's p50 and p95, the share of
ticks over the latency limit (one tick period), and how late the
generator ran in the first and the last fifth of the window. The knee is
the highest count whose p95 stays under the limit with no growing
lateness; a traffic file's ``sensors`` is set once, from such a sweep, at
four fifths of it. The benchmark's own runs never sweep. Needs a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def point(cell, sensors: int, seed: int, seconds: float) -> dict:
    import numpy as np

    from bench.driver import Session

    cell = dataclasses.replace(
        cell, traffic=dataclasses.replace(cell.traffic, sensors=sensors))
    sess = Session(cell, seed)
    sess.build()
    win = sess.run(seconds, None)
    lat = np.asarray([r.collected - r.due for r in win.ticks.values()
                      if r.collected is not None])
    late = np.asarray(win.generator_late_s)
    k = max(len(late) // 5, 1)
    limit = cell.traffic.period_s
    out = {"sensors": sensors, "ticks": len(win.ticks),
           "p50_ms": float(np.median(lat)) * 1e3,
           "p95_ms": float(np.percentile(lat, 95)) * 1e3,
           "over_limit": float((lat > limit).mean()),
           "late_first_fifth_ms": float(late[:k].mean()) * 1e3,
           "late_last_fifth_ms": float(late[-k:].mean()) * 1e3,
           "dispatch_p50_ms": float(np.median(
               [r.dispatch_s for r in win.ticks.values()])) * 1e3,
           "collect_p50_ms": float(np.median(
               [r.collect_s for r in win.ticks.values()])) * 1e3}
    sess.free_program()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--sensors", type=int, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    from bench import spec
    from bench.run import prepare_process

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    prepare_process()
    cell = spec.cell(args.workload)
    if cell.traffic.loop != "open":
        print("sweep: only an open-loop cell has a knee", file=sys.stderr)
        return 2
    for s in args.sensors:
        print(json.dumps(point(cell, s, args.seed, args.seconds)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
