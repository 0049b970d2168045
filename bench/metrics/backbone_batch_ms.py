"""Device milliseconds per backbone batch: the mean duration of the
detector step's module (``jit(detector_step)``) in the traced window."""

from bench.trace import BACKBONE


def read(ctx):
    mods = [m for name, m in ctx["trace"]["modules"].items()
            if BACKBONE in name and m["count"]]
    if not mods:
        return None
    return (sum(m["seconds"] for m in mods)
            / sum(m["count"] for m in mods) * 1e3)
