"""The entry point: its refusals, and whole runs of small cells on the CPU."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import run, spec
from bench.tests.tiny import CPU_PEAKS, tiny_cell

ROOT = spec.ROOT
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "radar-f32.saturate",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(proc):
    return proc.returncode != 0 and "{" not in proc.stdout


def test_refuses_without_a_tpu():
    proc = _run(ROOT)
    assert _no_result(proc), proc.stdout
    assert "needs a TPU" in proc.stderr


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    assert _no_result(_run(tmp_path))


def test_the_result_line_has_the_contract_keys_checks_last():
    line = run.result_line(True, 10, 0, {}, {}, None, {})
    assert list(line) == KEYS
    line = run.result_line(True, 10, 0, {}, {}, {"device_ops": []}, {})
    assert list(line) == KEYS[:5] + ["breakdown", "checks"]


@pytest.mark.parametrize("name,trace", [
    ("radar-f32.saturate", False), ("radar-f32.saturate", True),
    ("radar-f32.open60", False), ("cascade-hubert.burst", False)])
def test_a_small_run_is_correct_and_well_formed(name, trace):
    cell = tiny_cell(name)
    line = run.run_cell(cell, 2**33 + 7, 1.0, trace, jax.devices(),
                        CPU_PEAKS, time.perf_counter())
    assert list(line) == KEYS[:5] + (["breakdown"] if trace else []) \
        + ["checks"]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["checks"]) <= set(cell.config["limits"])
    assert "score_gap" in line["checks"] or "score_rms" in line["checks"]
    assert ("hp_mismatch" in line["checks"]) == cell.traffic.capture
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(line["metrics"]) <= {m["name"] for m in cell.per_layer}
    else:
        assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    json.dumps(line)


def test_same_seed_same_inputs():
    from bench.driver import Session

    a, b = (Session(tiny_cell("radar-f32.open60"), 2**40 + 1)
            for _ in range(2))
    for s in (a, b):
        s.build()
    assert (a.pool == b.pool).all()
    assert a.weights.t_score == b.weights.t_score
    assert pathlib.Path(run.__file__).name == "run.py"
