"""Cells of BENCHMARK.json cut to a size the CPU runs in seconds.

Only the sizes change (32x32 frames, 8x8 fragments, D=256, three sensors,
and the detector's ``tiny`` keys from its reference module); the traffic's
loop, capture control and the configuration's limits are the cell's own.
"""

from __future__ import annotations

import copy
import dataclasses

from bench import reference, spec
from bench.peaks import Peaks

#: stands in for a chip's peaks where a CPU test reads a roofline
CPU_PEAKS = Peaks(bf16_flops=1e12, int8_ops=2e12, hbm_bytes=1e11)

#: the open-loop cell, left out of BENCHMARK.json until its host stalls
#: are gone (PERF.md, Open questions); its traffic file is in
#: bench/traffic, and these entries are what BENCHMARK.json would take
LATER = {
    "workloads": [{"name": "radar-f32.open60", "config": "hs-radar-f32",
                   "traffic": "open60", "chips": 1, "why": "open loop"}],
    "end_to_end": [{"name": "decision_p95_ms", "unit": "ms",
                    "better": "lower", "bound": 0.15, "source": "host_clock",
                    "workloads": ["radar-f32.open60"]}],
}


def benchmark() -> dict:
    """BENCHMARK.json with the :data:`LATER` entries added."""
    bm = spec.load_benchmark()
    for key, entries in LATER.items():
        bm[key] = bm[key] + entries
    return bm


def tiny_cell(name: str, bm: dict | None = None) -> spec.Cell:
    """The cell ``name`` of ``bm`` (default: :func:`benchmark`), cut to the
    CPU's size."""
    c = spec.cell(name, benchmark() if bm is None else bm)
    cfg = copy.deepcopy(c.config)
    cfg["gate"].update(frame_h=32, frame_w=32, fragment=8, stride=4, dim=256,
                       block_d=128)
    cfg["training"].update(frames=16)
    if "detector" in cfg:
        cfg["detector"].update(reference.detector(cfg).tiny(cfg["detector"]))
    t = dataclasses.replace(c.traffic, sensors=3, chunk=4, pool_streams=2,
                            pool_frames=16,
                            frame_hz=40.0 if c.traffic.loop == "open" else 0.0)
    return dataclasses.replace(c, config=cfg, traffic=t)
