"""Benchmark driver — one module per paper table/figure.

Prints ``name,value,derived`` CSV rows per benchmark plus a summary.
``python -m benchmarks.run [--only table1] [--json-dir out/]`` —
``--json-dir`` additionally writes each suite's rows as
``BENCH_<suite>.json`` (``benchmarks.common.write_json``).
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

SUITES = ["table1_auc", "fig12_thresholds", "fig13_stride",
          "fig15_fragsize_dim", "fig16_speedup", "stream_throughput",
          "fleet_throughput", "serve_throughput", "adaptation",
          "int_datapath", "control_loop", "table3_energy",
          "hypersense_roofline", "roofline"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json-dir", metavar="DIR", default=None,
                    help="write each suite's rows as DIR/BENCH_<suite>"
                         ".json in addition to the CSV stdout")
    args = ap.parse_args()
    enable_compile_cache()

    failures = []
    for suite in SUITES:
        if args.only and args.only not in suite:
            continue
        t0 = time.time()
        print(f"\n===== {suite} =====", flush=True)
        try:
            mod = __import__(f"benchmarks.{suite}", fromlist=["run"])
            rows = mod.run()
            if args.json_dir:
                from benchmarks import common
                path = common.write_json(
                    args.json_dir + "/", suite, rows,
                    meta={"elapsed_s": round(time.time() - t0, 2)})
                print(f"[{suite}] json -> {path}")
            for row in rows:
                row = dict(row)
                name = row.pop("name")
                kv = ",".join(f"{k}={v}" for k, v in row.items())
                print(f"{name},{kv}")
            print(f"[{suite}] ok in {time.time() - t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(suite)
    if failures:
        print(f"\nFAILED suites: {failures}")
        return 1
    print("\nall benchmark suites passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
