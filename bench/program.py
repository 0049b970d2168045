"""What the program's own counters (``repro.launch.telemetry``) recorded
in this process, for the per-layer metrics that read them.

The registry holds every call of the run, warm-up ticks included. A
program without the module, or a counter that never ran, gives None.
"""

from __future__ import annotations


def counters() -> dict[str, int] | None:
    """Every counter of the process's registry, or None without one."""
    try:
        from repro.launch import telemetry
    except ImportError:
        return None
    return telemetry.snapshot()["counters"]


def ratio(num: tuple[str, ...], den: str) -> float | None:
    """The summed counters ``num`` over the counter ``den``, or None where
    none of ``num`` or ``den`` ran."""
    c = counters()
    if c is None or not c.get(den) or not any(n in c for n in num):
        return None
    return sum(c.get(n, 0) for n in num) / c[den]


def longest_spans(t0: float, t1: float) -> dict[str, float] | None:
    """Each program span's longest call that began between ``t0`` and
    ``t1`` (``time.perf_counter`` seconds), in seconds; None without the
    module."""
    try:
        from repro.launch import telemetry
    except ImportError:
        return None
    out = {}
    for name in telemetry.snapshot()["spans"]:
        for r in telemetry.records(name):
            if t0 <= r.start_ns * 1e-9 <= t1:
                out[name] = max(out.get(name, 0.0), r.seconds)
    return out
