"""Work of each kernel and step, computed from shapes alone.

These counts are the algorithm's, not the implementation's: padding the
hypervector width up to whole tiles, the slab overlap of a tile, pad
rows of a detector batch and recomputed work never count. So a later
change to how a step is implemented leaves its work unchanged. A
detector's forward FLOPs are its reference module's ``frame_flops``
(``bench/reference``).
"""

from __future__ import annotations


def windows(n: int, size: int, stride: int) -> int:
    """In-bounds sliding windows along one axis."""
    return max(0, (n - size) // stride + 1)


def gate_frame_flops(g: dict) -> float:
    """FLOPs of the computation-reuse projection for one frame.

    Each of the ``my`` row bands multiplies every element of its ``h``
    image rows once per base row against the ``D``-wide base:
    ``my * h * W * D`` multiply-adds (HyperSense, arXiv:2401.10267, Sec. IV).
    At the paper's point, 2 * 5 * 96 * 128 * 5000 = 614.4 MFLOP.
    """
    my = windows(g["frame_h"], g["fragment"], g["stride"])
    return 2.0 * my * g["fragment"] * g["frame_w"] * g["dim"]


def gate_frame_bytes(g: dict) -> float:
    """Least bytes the scoring kernel moves per frame: the float32 frame in
    and the three per-window partial sums out."""
    my = windows(g["frame_h"], g["fragment"], g["stride"])
    mx = windows(g["frame_w"], g["fragment"], g["stride"])
    return 4.0 * g["frame_h"] * g["frame_w"] + 3 * 4.0 * my * mx


def detector_tokens(g: dict, d: dict) -> int:
    """Patch tokens one frame unrolls to."""
    return (g["frame_h"] // d["patch"]) * (g["frame_w"] // d["patch"])

