#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell (``BENCHMARK.json`` ``workloads``)
names a configuration and a traffic mix; set-up makes the inputs and
weights from ``--seed``, builds the served path and warms up every shape
the window uses; the window then serves the mix for ``--seconds``; once it
has closed, the plain reference checks what the timed path produced.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a profiler trace of the
first seconds of the window), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit.
The same numbers are the last lines of stderr. Without a TPU, or with
fewer chips than the cell asks for, it exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import program, spec  # noqa: E402


def _err(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def end_to_end(sess, win, setup_s: float, seconds: float) -> dict:
    """Every end-to-end metric this window can give."""
    S, C = sess.t.sensors, sess.t.chunk
    ticks = list(win.ticks.values())
    done = lambda x: x is not None and x <= win.end
    out = {"setup_s": setup_s,
           "gate_frames_per_s": sum(S * C for r in ticks if done(r.collected))
           / seconds}
    if sess.casc is not None:
        # a frame is served when its decision is on the host and, if it
        # was captured, its logits too
        out["cascade_frames_per_s"] = sum(
            S * C - r.hp + r.hp_in_window for r in ticks
            if done(r.collected)) / seconds
    if sess.t.loop == "open":
        lat = [r.collected - r.due for r in ticks if r.collected is not None]
        if lat:
            out["decision_p95_ms"] = float(np.percentile(lat, 95)) * 1e3
    return out


def window_lines(sess, win, seconds: float) -> list[str]:
    S, C = sess.t.sensors, sess.t.chunk
    ticks = list(win.ticks.values())
    n_done = sum(1 for r in ticks if r.collected is not None
                 and r.collected <= win.end)
    lines = [f"window: {seconds:g} s, {len(ticks)} ticks offered of "
             f"{S} sensors x {C} frames, {n_done} collected inside it; "
             f"compiles inside it: {win.compiles}",
             "window: host ms per tick: dispatch "
             f"{_ms([r.dispatch_s for r in ticks])}, collect "
             f"{_ms([r.collect_s for r in ticks])}"]
    done = sorted((r for r in ticks if r.collected is not None),
                  key=lambda r: r.collected)
    if len(done) > 2:
        gaps = np.diff([r.collected for r in done])
        worst = int(np.argmax(gaps))
        cpu = np.diff([r.usage for r in done], axis=0)
        a, b = done[worst].collected, done[worst + 1].collected
        disp = max((r.dispatch_s for r in ticks if a <= r.dispatched <= b),
                   default=0.0)
        lines.append(
            f"window: ms between collects p50 {np.median(gaps) * 1e3:.4f} "
            f"max {gaps[worst] * 1e3:.4f} at {b - win.t0:.3f} s, in it a "
            f"collect of {done[worst + 1].collect_s * 1e3:.4f} ms and a "
            f"dispatch of at most {disp * 1e3:.4f} ms, "
            f"CPU user/sys {cpu[worst, 0]:.2f}/{cpu[worst, 1]:.2f} s "
            f"(p50 {np.median(cpu[:, 0]):.2f}/{np.median(cpu[:, 1]):.2f}); "
            f"{int((gaps > 3 * np.median(gaps)).sum())} over 3x the median")
        inside = program.longest_spans(a, b)
        if inside:
            lines.append("window: in that gap the program's longest spans, "
                         "ms: " + ", ".join(
                             f"{k} {v * 1e3:.4f}"
                             for k, v in sorted(inside.items())))
    if sess.casc is not None:
        lines.append(f"window: {sum(r.hp for r in ticks)} HP frames, "
                     f"{sess.cascade_batches} backbone batches in all")
    if win.generator_late_s:
        late = np.asarray(win.generator_late_s)
        lat = np.asarray([r.collected - r.due for r in ticks
                          if r.collected is not None])
        limit = sess.t.period_s
        lines.append(
            f"window: generator late ms p50 {np.median(late) * 1e3:.4f} "
            f"p95 {np.percentile(late, 95) * 1e3:.4f} max "
            f"{late.max() * 1e3:.4f}; decision latency ms p50 "
            f"{np.median(lat) * 1e3:.4f} p95 "
            f"{np.percentile(lat, 95) * 1e3:.4f} max {lat.max() * 1e3:.4f}; "
            f"share over the {limit * 1e3:.4g} ms limit "
            f"{float((lat > limit).mean()):.6f}; lateness trend ms "
            f"first/last fifth {_trend(late)}")
    return lines


def _ms(xs) -> str:
    if not xs:
        return "none"
    a = np.asarray(xs) * 1e3
    return f"p50 {np.median(a):.4f} p95 {np.percentile(a, 95):.4f}"


def _trend(late: np.ndarray) -> str:
    k = max(len(late) // 5, 1)
    return f"{late[:k].mean() * 1e3:.4f}/{late[-k:].mean() * 1e3:.4f}"


def per_layer(sess, win, cell, peaks, trace_dir: str):
    """``(metrics, device extras, breakdown)`` from the traced window."""
    from bench import trace as trace_mod

    tr = trace_mod.reduce(trace_mod.from_xplane(trace_dir))
    lo, hi = win.trace_t0, win.trace_t1
    S, C = sess.t.sensors, sess.t.chunk
    inside = lambda x: x is not None and lo <= x <= hi
    ctx = {
        "trace": tr, "config": cell.config, "gate": sess.g,
        "detector": sess.d, "peaks": peaks, "chips": cell.chips,
        "frames_per_kernel_call": sess.svc.n_slots * C / max(
            len(tr["devices"]), 1),
        "counts": {
            "gate_frames": sum(S * C for r in win.ticks.values()
                               if inside(r.collected)),
            "backbone_frames": sum(m for t, m in sess.batch_log
                                   if inside(t)),
        },
    }
    metrics = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    print(f"trace: window {tr['window_s']:.6f} s, busy {tr['busy_s']:.6f} s, "
          f"kernel {tr['kernel_s']:.6f} s in {tr['kernel_calls']} calls, "
          f"spans {tr['spans']}, modules "
          f"{ {k: v for k, v in tr['modules'].items() if v['count'] > 2} }",
          flush=True)
    device = {"busy_s": tr["busy_s"], "window_s": tr["window_s"]}
    breakdown = {"device_ops": tr["device_ops"],
                 "idle_gaps": tr["idle_gaps"]}
    return metrics, device, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        _err("--seed must be >= 0 and --seconds > 0")
        return 2
    try:
        cell = spec.cell(args.workload)
    except (KeyError, FileNotFoundError, TypeError, ValueError) as e:
        _err(f"cannot load workload {args.workload!r}: {e}")
        return 2

    cache = prepare_process()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _err(f"needs a TPU; JAX found {devices[0].platform!r}; nothing run")
        return 1
    if len(devices) < cell.chips:
        _err(f"{cell.name} needs {cell.chips} chips, JAX found "
             f"{len(devices)}; nothing run")
        return 1
    from bench.peaks import peaks_for

    try:
        peaks = peaks_for(devices[0].device_kind)
    except KeyError as e:
        _err(str(e))
        return 1
    try:
        import repro.launch.serve  # noqa: F401
    except ImportError as e:
        _err(f"the program is not importable: {e}")
        return 1
    print(f"cell {cell.name}: seed {args.seed}, {args.seconds:g} s, trace "
          f"{args.trace}, compile cache {cache}", flush=True)
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                    peaks, T_START)
    for k, v in line["checks"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


#: glibc ``mallopt`` parameters
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3


def prepare_process() -> str:
    """Fix what would otherwise differ from one run to the next; returns
    the compile cache's directory.

    - JAX's persistent compilation cache lives at ``<checkout>/.jax_cache``,
      whatever the environment says, and keeps every program, so only the
      first run of a cell in a checkout compiles.
    - glibc's malloc serves large buffers from its heap and keeps what is
      freed there (mmap threshold at its 32 MiB ceiling, trim threshold
      1 GiB). Left to itself, glibc moves both thresholds by what the
      process happened to free first, and a run lands in one state or the
      other: the served path allocates a fresh super-chunk every tick, and
      its dispatch took 5.7 ms a tick in one state and 18.5 ms in the
      other, at the same load on a TPU v5e host (PERF.md).
    """
    import ctypes

    import jax

    try:
        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError):
        mallopt = None              # not glibc: nothing to fix
    if mallopt is not None:
        mallopt.argtypes, mallopt.restype = (ctypes.c_int, ctypes.c_int), \
            ctypes.c_int
        mallopt(M_MMAP_THRESHOLD, 32 << 20)
        mallopt(M_TRIM_THRESHOLD, 1 << 30)
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, peaks,
             t_start: float) -> dict:
    """Set-up, window and check of one cell; returns the result line.

    Everything a run does after its look for the chips: ``main`` calls it
    on a TPU, the tests on the CPU at a small size.
    """
    from bench import check
    from bench.driver import Session

    sess = Session(cell, seed)
    # imports, the runtime's start and the look for the chips
    sess.setup["process_start"] = time.perf_counter() - t_start
    sess.build()
    setup_s = time.perf_counter() - t_start
    print("setup: " + ", ".join(f"{k} {v:.4f} s" for k, v in
                                sess.setup.items())
          + f"; total {setup_s:.4f} s; t_score {sess.weights.t_score!r}",
          flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    steps = [sess.svc.compile_count()]
    if sess.casc is not None:
        steps.append(sess.casc.compile_count())
    try:
        win = sess.run(seconds, trace_dir)
        steps = [sess.svc.compile_count() - steps[0]] + (
            [sess.casc.compile_count() - steps[1]] if len(steps) > 1 else [])
        win.compiles["step_compiles"] = steps
        for text in window_lines(sess, win, seconds):
            print(text, flush=True)
        mem = sess.memory_peak()
        S, C = sess.t.sensors, sess.t.chunk
        attempted = len(win.ticks) * S * C
        failed = (sum(S * C for r in win.ticks.values()
                      if r.collected is None) + sess.svc.hp_dropped)
        extra, breakdown = {}, None
        if trace:
            metrics, extra, breakdown = per_layer(sess, win, cell, peaks,
                                                  trace_dir)
        else:
            e2e = end_to_end(sess, win, setup_s, seconds)
            metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    sess.free_program()
    t0 = time.perf_counter()
    ok, checks, readings = check.run_check(sess)
    print(f"check: readings {readings}; reference "
          f"{time.perf_counter() - t0:.4f} s", flush=True)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem, **extra}
    return result_line(ok, attempted, failed, metrics, device, breakdown,
                       checks)


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown: dict | None, checks: dict) -> dict:
    """The last line of stdout, its keys in order, ``checks`` last."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


if __name__ == "__main__":
    sys.exit(main())
