"""The per-layer metrics read from the program's own counters
(``bench/program.py``): small traced runs of both cells on the CPU report
each of them, and a program that records nothing gives None, not an
error."""

import sys
import time

import jax
import pytest

from bench import run, spec
from bench.tests.tiny import CPU_PEAKS, tiny_cell

PROGRAM_METRICS = {"h2d_bytes_per_frame", "gate_sampled_share"}


def _program_metrics(cell):
    return [m["name"] for m in cell.per_layer
            if m["name"].split(".", 1)[0] in PROGRAM_METRICS]


@pytest.mark.parametrize("name", ["radar-f32.saturate",
                                  "cascade-hubert.burst"])
def test_a_traced_run_reports_every_program_metric(name):
    from repro.launch import telemetry

    cell = tiny_cell(name)
    names = _program_metrics(cell)
    assert names
    telemetry.reset()
    line = run.run_cell(cell, 2**32 + 11, 1.0, True, jax.devices(),
                        CPU_PEAKS, time.perf_counter())
    assert line["correct"] is True, line["checks"]
    got = line["metrics"]
    for n in names:
        assert n in got and got[n]["value"] > 0, n
    if "gate_sampled_share.cascade_fps" in names:
        assert got["gate_sampled_share.cascade_fps"]["value"] <= 100.0
    # dispatch uploads the float frames and a few bytes of per-tick
    # metadata; with capture on, the HP capture uploads the raw frames
    # again and the detector's blocks go up besides
    g = cell.config["gate"]
    px = g["frame_h"] * g["frame_w"] * 4
    (h2d,) = [v["value"] for k, v in got.items()
              if k.startswith("h2d_bytes_per_frame.")]
    if "gate_sampled_share.cascade_fps" in names:
        assert h2d > 2 * px
    else:
        assert px < h2d < px + 64


def test_a_program_without_telemetry_gives_none(monkeypatch):
    import repro.launch

    monkeypatch.setitem(sys.modules, "repro.launch.telemetry", None)
    monkeypatch.delattr(repro.launch, "telemetry", raising=False)
    for base in sorted(PROGRAM_METRICS):
        assert spec.metric_reader(base)({}) is None, base
