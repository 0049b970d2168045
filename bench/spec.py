"""``BENCHMARK.json`` and the files it names, found by name.

A cell is one entry of ``workloads``. Its configuration is the JSON file
the ``configs`` entry names; its traffic mix is
``bench/traffic/<traffic>.json``; a per-layer metric ``q.suffix`` is read
by ``bench/metrics/q.suffix.py`` if that file exists, else by
``bench/metrics/q.py``. Adding a configuration, a mix or a metric
therefore adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

from bench.generator import Traffic

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: Traffic
    end_to_end: list        # entries of end_to_end this cell reports
    per_layer: list         # entries of per_layer this cell reports


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_traffic(name: str, bench: pathlib.Path = BENCH) -> Traffic:
    data = json.loads((bench / "traffic" / f"{name}.json").read_text())
    data.pop("why", None)
    t = Traffic(name=name, **data)
    t.validate()
    return t


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bm: dict | None = None,
         root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``bm`` (default: the repo's BENCHMARK.json)."""
    bm = load_benchmark(root) if bm is None else bm
    work = {w["name"]: w for w in bm["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    e2e = [m for m in bm["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"]
             if m["moves"] in reported and _reports(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=load_traffic(w["traffic"], root / "bench"),
                end_to_end=e2e, per_layer=layer)


def metric_reader(name: str, bench: pathlib.Path = BENCH):
    """The ``read(ctx)`` function of per-layer metric ``name``."""
    d = bench / "metrics"
    path = d / f"{name}.py"
    if not path.exists():
        path = d / f"{name.split('.', 1)[0]}.py"
    if not path.exists():
        raise KeyError(f"no reader for metric {name!r} under {d}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
