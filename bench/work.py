"""Work of each kernel and step, computed from shapes alone.

These counts are the algorithm's, not the implementation's: padding the
hypervector width up to whole tiles, the slab overlap of a tile, pad
rows of a detector batch and recomputed work never count. So a later
change to how a step is implemented leaves its work unchanged.
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


def backbone_frame_flops(g: dict, d: dict) -> float:
    """Forward FLOPs of the encoder layers for one real frame.

    Per token and layer: Q, K, V and output projections (8 d^2), the
    feed-forward up and down projections (4 d d_ff), and the attention
    scores and weighted sum over ``s`` tokens (4 s d). With d_ff = 4 d this
    is the familiar 24 d^2 + 4 s d; for hubert-xlarge at 128x128 frames in
    8x8 patches, 256 tokens x 48 layers come to about 0.499 TFLOP.
    """
    s = detector_tokens(g, d)
    dm, f = d["d_model"], d["d_ff"]
    per_token = 8 * dm * dm + 4 * dm * f + 4 * s * dm
    return float(s * d["n_layers"] * per_token)
