"""A second detector joins the benchmark by files and entries alone.

A stand-in second detector, the program's ``dense`` family as an embeds-in
detector (RMS norm, gated SiLU feed-forward, grouped-query causal
attention), runs through ``run.run_cell`` from an in-memory benchmark, the
way ``tiny.LATER`` adds a cell: its configuration file names its own
reference module (``bench/tests/dense_ref.py``), one workload entry adds
the cell, and the cascade's metrics list it among their workloads.
"""

import json
import time
import types

import jax
import pytest

from bench import check, reference, run, spec
from bench.driver import Session
from bench.reference import hubert
from bench.tests import dense_ref, tiny
from bench.tests.tiny import CPU_PEAKS

NAME = "cascade-dense.burst"
CONFIG = "hs-cascade-dense-standin"
REF = "bench/tests/dense_ref.py"
SEED = 2**36 + 5

#: the detector at the sizes of a dense GQA decoder (internlm2-1.8b's);
#: the tests run it at its reference's ``tiny`` keys
DETECTOR = {
    "arch_id": "dense-standin", "family": "dense", "n_layers": 24,
    "d_model": 2048, "n_heads": 16, "kv_heads": 8, "d_ff": 8192,
    "vocab": 92544, "norm": "rmsnorm", "activation": "silu",
    "is_encoder": False, "causal": True, "embeds_in": True,
    "rope_theta": 10000.0, "scan_layers": True, "compute_dtype": "bfloat16",
    "param_dtype": "float32", "patch": 8, "batch": 8, "n_out": 2,
    "max_inflight": 2,
}


def _benchmark(tmp_path) -> dict:
    """BENCHMARK.json with the stand-in's configuration and cell added."""
    cfg = json.loads((spec.BENCH / "configs" / "hs-cascade-hubert-xlarge.json")
                     .read_text())
    for key in ("detector_source", "detector_shape"):
        cfg.pop(key)
    cfg.update(name=CONFIG, reference=f"{reference.GATE}, {REF}",
               detector=DETECTOR)
    path = tmp_path / f"{CONFIG}.json"
    path.write_text(json.dumps(cfg))
    bm = tiny.benchmark()
    bm["configs"] = bm["configs"] + [{
        "name": CONFIG, "source": "a test", "file": str(path),
        "reduced": [], "why": "a stand-in second detector"}]
    bm["workloads"] = bm["workloads"] + [{
        "name": NAME, "config": CONFIG, "traffic": "burst", "chips": 1,
        "why": "a test"}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "cascade-hubert.burst" in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [NAME]
    return bm


def _line(cell):
    return run.run_cell(cell, SEED, 1.0, False, jax.devices(), CPU_PEAKS,
                        time.perf_counter())


def test_a_second_detector_runs_correct(tmp_path):
    cell = tiny.tiny_cell(NAME, _benchmark(tmp_path))
    d = cell.config["detector"]
    assert d["family"] == "dense" and d["kv_heads"] < d["n_heads"]
    assert d["n_layers"] == dense_ref.tiny(DETECTOR)["n_layers"]
    line = _line(cell)
    assert line["correct"] is True, line["checks"]
    assert "logit_noise_ratio" in line["checks"]
    assert set(line["metrics"]) == {"cascade_frames_per_s", "setup_s"}


def test_hubert_s_reference_cannot_check_a_second_detector(
        tmp_path, monkeypatch):
    """The check run against another detector's reference reads weights
    that are not in that reference's tree: the run raises and prints no
    result."""
    cell = tiny.tiny_cell(NAME, _benchmark(tmp_path))
    monkeypatch.setattr(check, "reference", types.SimpleNamespace(
        detector=lambda config: hubert))
    with pytest.raises(KeyError):
        _line(cell)


def test_the_check_holds_a_second_detector_to_its_own_reference(
        tmp_path, monkeypatch):
    """A program that runs another network than its reference (here
    bidirectional where the configuration says causal) fails the check on
    ``logit_noise_ratio``."""
    cell = tiny.tiny_cell(NAME, _benchmark(tmp_path))
    build = Session._build_cascade

    def build_bidirectional(self):
        self.d = dict(self.d, causal=False)
        build(self)

    monkeypatch.setattr(Session, "_build_cascade", build_bidirectional)
    line = _line(cell)
    assert line["correct"] is False
    c = line["checks"]["logit_noise_ratio"]
    assert c["value"] > c["limit"], c


def test_backbone_mfu_counts_the_detector_s_own_flops(tmp_path):
    cell = spec.cell(NAME, _benchmark(tmp_path))
    g, d = cell.config["gate"], cell.config["detector"]
    ctx = {"counts": {"backbone_frames": 10}, "config": cell.config,
           "gate": g, "detector": d, "trace": {"window_s": 2.0}, "chips": 1,
           "peaks": CPU_PEAKS}
    mfu = spec.metric_reader("backbone_step_mfu.cascade_fps")(ctx)
    assert mfu == 10 * dense_ref.frame_flops(g, d) / (
        2.0 * CPU_PEAKS.bf16_flops) * 100.0
    assert dense_ref.frame_flops(g, d) != hubert.frame_flops(g, d)
