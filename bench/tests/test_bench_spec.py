"""BENCHMARK.json, and the files it names, found by name."""

import json
import re
import shutil

import pytest

from bench import spec

BM = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_command():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51


def test_names_units_and_entry_keys():
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(names) == len(set(names))
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("name", [w["name"] for w in BM["workloads"]])
def test_every_cell_loads_and_reports_what_it_must(name):
    cell = spec.cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    assert all(m["moves"] in e2e for m in cell.per_layer)
    assert cell.config["name"] == next(
        w["config"] for w in BM["workloads"] if w["name"] == name)


@pytest.mark.parametrize("name", [m["name"] for m in BM["per_layer"]])
def test_every_per_layer_metric_has_a_reader(name):
    assert callable(spec.metric_reader(name))


def test_roofline_and_mfu_names():
    for m in BM["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_a_new_config_mix_and_metric_need_only_new_files(tmp_path):
    """Add a configuration, a traffic mix and a per-layer metric as new
    files plus new entries: the harness finds all three by name."""
    root = tmp_path
    shutil.copytree(spec.BENCH, root / "bench")
    bm = json.loads(json.dumps(BM))
    cfg = json.loads((spec.BENCH / "configs" / "hs-radar-f32.json")
                     .read_text())
    cfg["name"] = "dummy-config"
    (root / "bench" / "configs" / "dummy-config.json").write_text(
        json.dumps(cfg))
    mix = json.loads((spec.BENCH / "traffic" / "saturate.json").read_text())
    mix["sensors"] = 5
    (root / "bench" / "traffic" / "dummy-mix.json").write_text(
        json.dumps(mix))
    (root / "bench" / "metrics" / "dummy_ms.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bm["configs"].append({"name": "dummy-config", "source": "x",
                          "file": "bench/configs/dummy-config.json",
                          "reduced": [], "why": "a test"})
    bm["workloads"].append({"name": "dummy.cell", "config": "dummy-config",
                            "traffic": "dummy-mix", "chips": 1,
                            "why": "a test"})
    bm["end_to_end"][0].setdefault("workloads", []).append("dummy.cell")
    moves = bm["end_to_end"][0]["name"]
    bm["per_layer"].append({"name": "dummy_ms.x", "unit": "ms",
                            "better": "lower", "source": "program_span",
                            "layer": "a test", "moves": moves,
                            "workloads": ["dummy.cell"]})
    cell = spec.cell("dummy.cell", bm, root=root)
    assert cell.config["name"] == "dummy-config"
    assert cell.traffic.sensors == 5 and cell.traffic.name == "dummy-mix"
    assert [m["name"] for m in cell.per_layer] == ["dummy_ms.x"]
    assert spec.metric_reader("dummy_ms.x", bench=root / "bench")({}) == 42.0


def test_an_unknown_cell_or_metric_is_an_error():
    with pytest.raises(KeyError):
        spec.cell("no-such.cell")
    with pytest.raises(KeyError):
        spec.metric_reader("no_such_metric.x")
