"""The detector reference a configuration names, found by its name."""

import json
import pathlib

import pytest

from bench import reference, spec
from bench.reference import hubert

CONFIGS = {c["name"]: json.loads((spec.ROOT / c["file"]).read_text())
           for c in spec.load_benchmark()["configs"]}
CASCADE = CONFIGS["hs-cascade-hubert-xlarge"]


def test_the_cascade_configuration_resolves_to_hubert():
    mod = reference.detector(CASCADE)
    assert mod is hubert
    assert pathlib.Path(mod.__file__) == spec.BENCH / "reference" / "hubert.py"


@pytest.mark.parametrize("name", [n for n, c in CONFIGS.items()
                                  if "detector" in c])
def test_every_detector_configuration_keeps_the_contract(name):
    cfg = CONFIGS[name]
    mod = reference.detector(cfg)
    assert all(callable(getattr(mod, f)) for f in reference.CONTRACT)
    assert mod.frame_flops(cfg["gate"], cfg["detector"]) > 0
    assert set(mod.tiny(cfg["detector"])) <= set(cfg["detector"])


@pytest.mark.parametrize("named", [
    "bench/reference/gate.py",
    "",
    "bench/reference/gate.py, bench/reference/hubert.py, "
    "bench/tests/dense_ref.py",
    "bench/reference/gate.py, bench/reference/hubert-xlarge.py",
    "bench/reference/gate.py, /abs/hubert.py",
], ids=["gate-only", "none", "two-detectors", "not-a-module-name",
        "absolute"])
def test_a_detector_without_one_detector_reference_is_an_error(named):
    cfg = dict(CASCADE, reference=named)
    with pytest.raises(ValueError, match="detector reference"):
        reference.detector(cfg)


def test_a_module_without_frame_flops_is_an_error(tmp_path, monkeypatch):
    (tmp_path / "flopless_ref.py").write_text(
        "def make_weights(key, g, d):\n    return {}\n\n"
        "def logits(params, frames, d, *, mode='bfloat16'):\n    return None\n\n"
        "def tiny(d):\n    return {}\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    cfg = dict(CASCADE, reference=f"{reference.GATE}, flopless_ref.py")
    with pytest.raises(ValueError, match="frame_flops"):
        reference.detector(cfg)
