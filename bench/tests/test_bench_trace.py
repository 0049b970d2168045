"""The trace reduction, on a trace recorded on a TPU v5e and cut to 0.3 s
of the ``radar-f32.saturate`` window (``data/trace_saturate.json``)."""

import pathlib

import numpy as np
import pytest

from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(DATA / "trace_saturate.json"))


def _busy_by_bins(tr, lo, hi, step=1000.0):
    """Busy nanoseconds counted on a 1 us grid: the slow, obvious way."""
    n = int((hi - lo) // step)
    busy = np.zeros(n, bool)
    for _, _, s, d in tr.ops:
        a = int(max(0, (s - lo) // step))
        b = int(min(n, -(-(s + d - lo) // step)))
        busy[a:b] = True
    return busy.sum() * step


def test_union_merges_and_clips():
    got = trace.union([(5, 9), (0, 2), (1, 3), (8, 12), (20, 30)], 1, 25)
    assert got == [[1, 3], [5, 12], [20, 25]]


def test_busy_union_matches_a_grid_count(recorded):
    red = trace.reduce(recorded)
    lo, hi = trace.window(recorded)
    assert red["window_s"] == pytest.approx(0.3)
    assert red["devices"] == [0]
    grid = _busy_by_bins(recorded, lo, hi) * 1e-9
    assert red["busy_s"] == pytest.approx(grid, abs=2e-6 * len(recorded.ops))
    assert 0 < red["busy_s"] <= red["window_s"]


def test_idle_share_is_the_rest_of_the_window(recorded):
    from bench.metrics import device_idle_share

    red = trace.reduce(recorded)
    share = device_idle_share.read({"trace": red})
    assert share == pytest.approx((1 - red["busy_s"] / 0.3) * 100)
    assert 0 <= share < 100


def test_kernel_time_is_the_sum_of_its_events(recorded):
    red = trace.reduce(recorded)
    lo, hi = trace.window(recorded)
    kern = [o for o in recorded.ops
            if o[1].startswith("hypersense_scores") and lo <= o[2] < hi]
    assert red["kernel_calls"] == len(kern) == 6
    assert red["kernel_s"] == pytest.approx(sum(o[3] for o in kern) * 1e-9)
    # one (512, 128, 128) float32 call of the scoring kernel: 49.07 ms
    assert red["kernel_s"] / red["kernel_calls"] == pytest.approx(0.04907,
                                                                 rel=1e-3)


def test_spans_and_gaps_are_named(recorded):
    red = trace.reduce(recorded)
    assert red["spans"]["dispatch"]["count"] == 7
    assert set(red["spans"]) <= {"dispatch", "collect", "drain", "pump",
                                 "wait"}
    assert len(red["idle_gaps"]) <= 10
    assert all(name in {"dispatch", "collect", "none"} and s > 0
               for name, s in red["idle_gaps"])
    assert red["device_ops"][0][0].startswith("hypersense_scores")


def test_a_trace_without_a_window_span_is_refused(recorded):
    bare = trace.Trace(recorded.ops, recorded.modules,
                       [s for s in recorded.spans if s[0] != "bench.window"])
    with pytest.raises(ValueError):
        trace.reduce(bare)
