#!/usr/bin/env python3
"""Readings of the check's numbers: the program's and the control's.

    python3 bench/control.py --workload <name> --seconds 5 --seeds 1 2 3

For each seed, in one process: set up the cell as a run does, serve a
short window at the cell's own load, then compute every number the check
compares twice, once for the program's outputs (its lower reading) and
once for the control's (its upper reading: the reference one precision
below the configuration's, put in the program's place), and the gate's
scores once more with the reference at ``high``. One JSON line per seed. The limits in a configuration file are set from these readings; the
benchmark's own runs never run the control. Needs a TPU, like a run.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, seconds: float) -> dict:
    from bench import check
    from bench.driver import Session

    t0 = time.perf_counter()
    sess = Session(cell, seed)
    sess.build()
    sess.run(seconds, None)
    sess.free_program()
    out = check.program_outputs(sess)
    ref = check.Reference(sess, out.scores.shape[1])
    keys = check.logit_sample(sess, out) if sess.d is not None else []
    ref_out = ref.outputs(keys)
    prog, cover = check.numbers(sess, out, ref, ref_out)
    ctl, _ = check.numbers(sess, check.control_outputs(sess, ref, keys), ref,
                          ref_out)
    high, _ = check.numbers(
        sess, check.control_outputs(sess, ref, keys, precision="high"), ref,
        ref_out)
    return {"seed": seed, "program": prog, "control": ctl,
            "control_high": {k: high[k] for k in ("score_gap", "score_rms")},
            "cover": cover,
            "t_score": sess.weights.t_score,
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import spec

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    from bench.run import prepare_process

    prepare_process()
    cell = spec.cell(args.workload)
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.seconds)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
