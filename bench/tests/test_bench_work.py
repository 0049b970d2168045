"""Work counts against hand counts."""

import json
import pathlib

from bench import reference, work

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_gate_flops_at_the_papers_point():
    g = _cfg("hs-radar-f32")["gate"]
    # 5 row bands x 96 rows x 128 columns x D=5000, two FLOPs each
    assert work.gate_frame_flops(g) == 2 * 5 * 96 * 128 * 5000 == 614.4e6


def test_gate_bytes_are_the_frame_and_the_partial_sums():
    g = _cfg("hs-radar-f32")["gate"]
    assert work.gate_frame_bytes(g) == 4 * 128 * 128 + 3 * 4 * 5 * 5


def test_hubert_forward_per_frame():
    """The cascade's detector FLOPs are its reference module's count."""
    c = _cfg("hs-cascade-hubert-xlarge")
    d, s = 1280, 256
    want = s * 48 * (24 * d * d + 4 * s * d)
    assert work.detector_tokens(c["gate"], c["detector"]) == s
    flops = reference.detector(c).frame_flops(c["gate"], c["detector"])
    assert flops == want
    assert abs(want - 0.499e12) < 0.001e12
