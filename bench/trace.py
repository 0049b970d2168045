"""From the profiler's trace to the numbers the per-layer metrics read.

:func:`from_xplane` keeps three kinds of event from the ``.xplane.pb`` the
JAX profiler writes: the operations each device ran (the ``XLA Ops``
line of each ``/device:TPU:n`` plane; ``XLA Modules`` where a device has
no op line), the modules (jitted programs) it ran, and the host spans the
benchmark opened around each call into the program (names starting with
``bench.``). :func:`reduce` turns them into busy time, kernel time, span
times and the breakdown. A small trace in this reduced form is kept with
the tests, so the reduction is checked without a chip.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os

#: the scoring kernel's stable name (its ``pallas_call`` name)
KERNEL = "hypersense_scores"
#: the detector backbone's jitted step
BACKBONE = "detector_step"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: list        # [device, name, start_ns, dur_ns]
    modules: list    # [device, name, start_ns, dur_ns]
    spans: list      # [name, start_ns, dur_ns]

    @classmethod
    def from_dict(cls, d: dict) -> "Trace":
        return cls(d["ops"], d["modules"], d["spans"])


def _label(ev) -> str:
    """An op's HLO name (``fusion.5``), with the kernel's name appended
    where only a stat carries it."""
    name = ev.name.split(" = ", 1)[0].lstrip("%")
    if KERNEL in name:
        return name
    for _, v in ev.stats:
        if isinstance(v, str) and KERNEL in v:
            return f"{name} {KERNEL}"
    return name


def from_xplane(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            try:
                dev = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            op_line = lines.get("XLA Ops", lines.get("XLA Modules"))
            if op_line is not None:
                ops += [[dev, _label(e), e.start_ns, e.duration_ns]
                        for e in op_line.events]
            if "XLA Modules" in lines:
                modules += [[dev, e.name, e.start_ns, e.duration_ns]
                            for e in lines["XLA Modules"].events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in ln.events
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(ops, modules, spans)


def union(intervals, lo: float, hi: float) -> list:
    """Disjoint ``[start, end)`` cover of ``intervals`` clipped to the window."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window(tr: Trace) -> tuple[float, float]:
    w = [s for s in tr.spans if s[0] == SPAN_PREFIX + "window"]
    if not w:
        raise ValueError("the trace holds no bench.window span")
    _, start, dur = max(w, key=lambda s: s[2])
    return start, start + dur


def reduce(tr: Trace) -> dict:
    """Busy time, kernel time, spans and the breakdown of the window."""
    lo, hi = window(tr)
    devices = sorted({o[0] for o in tr.ops})
    busy, covers = {}, {}
    for d in devices:
        covers[d] = union(((o[2], o[2] + o[3]) for o in tr.ops if o[0] == d),
                          lo, hi)
        busy[d] = sum(e - s for s, e in covers[d])
    inside = lambda ev_start: lo <= ev_start < hi
    kern = [o for o in tr.ops if KERNEL in o[1] and inside(o[2])]
    mods = collections.defaultdict(lambda: [0, 0.0])
    for d, name, s, dur in tr.modules:
        if inside(s):
            mods[name][0] += 1
            mods[name][1] += dur
    spans = collections.defaultdict(lambda: [0, 0.0])
    for name, s, dur in tr.spans:
        if name != SPAN_PREFIX + "window" and inside(s):
            spans[name[len(SPAN_PREFIX):]][0] += 1
            spans[name[len(SPAN_PREFIX):]][1] += dur
    by_op = collections.Counter()
    for d, name, s, dur in tr.ops:
        if inside(s):
            by_op[name] += dur
    n_dev = max(len(devices), 1)
    return {
        "window_s": (hi - lo) * 1e-9,
        "devices": devices,
        "busy_s": sum(busy.values()) / n_dev * 1e-9,
        "kernel_s": sum(o[3] for o in kern) * 1e-9,
        "kernel_calls": len(kern),
        "modules": {k: {"count": v[0], "seconds": v[1] * 1e-9}
                    for k, v in mods.items()},
        "spans": {k: {"count": v[0], "seconds": v[1] * 1e-9}
                  for k, v in spans.items()},
        "device_ops": [[n, t * 1e-9 / n_dev]
                       for n, t in by_op.most_common(10)],
        "idle_gaps": idle_gaps(tr, covers.get(devices[0], []) if devices
                               else [], lo, hi),
    }


def idle_gaps(tr: Trace, cover: list, lo: float, hi: float,
              top: int = 10) -> list:
    """The longest idle gaps of one device, each named by the host span
    that overlaps it most (``none`` where the host was in no span)."""
    gaps, prev = [], lo
    for s, e in cover:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    spans = [(n[len(SPAN_PREFIX):], s, s + d) for n, s, d in tr.spans
             if n != SPAN_PREFIX + "window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, best_ov = "none", 0.0
        for name, a, b in spans:
            ov = min(e, b) - max(s, a)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append([best, (e - s) * 1e-9])
    return out


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_dict(json.load(f))
