"""Spans and counters of the served path (repro/launch/telemetry.py).

* spans nest: each records its parent and the tick's ``seq``, which
  children inherit; counters add; the per-name ring stays bounded;
* ``snapshot()`` / ``reset()``;
* ``FleetService`` records one of each dispatch and collect span per tick,
  counts the bytes it uploads and the frames it scores and samples; a
  tick collected by dispatch's back-pressure is not inside its span;
* ``CascadeService`` records one launch and one wait span per batch and
  counts its blocks' bytes;
* the gate step's named scopes reach the lowered program.
"""

import jax
import numpy as np
import pytest

from repro.core import encoding, hypersense
from repro.core.sensor_control import CaptureConfig, ControllerConfig
from repro.launch import telemetry
from repro.launch.serve import FleetService

C = 4                                   # chunk size
HW = (16, 16)
CFG = ControllerConfig(hold_frames=2, base_rate_hz=10.0, active_rate_hz=30.0)

DISPATCH = ("serve.dispatch", "serve.dispatch.assemble",
            "serve.dispatch.upload", "serve.dispatch.launch")
COLLECT = ("serve.collect", "serve.collect.wait", "serve.collect.hp_capture")


@pytest.fixture
def reg():
    return telemetry.Registry()


def make_model(t_score=-0.05):
    B0, b = encoding.make_perm_base_rows(jax.random.PRNGKey(1), 6, 64)
    chvs = jax.random.normal(jax.random.PRNGKey(2), (2, 64))
    return hypersense.HyperSenseModel(chvs, B0, b, 6, 6, 3, t_score=t_score,
                                      t_detection=1)


def frames(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, C, *HW)).astype(np.float32)


def test_nested_spans_record_parent_and_seq(reg):
    with reg.span("a", seq=7):
        with reg.span("a.b"):
            with reg.span("a.b.c", seq=9):
                pass
    with reg.span("free"):
        pass
    (a,), (b,), (c,), (free,) = (reg.records(n)
                                 for n in ("a", "a.b", "a.b.c", "free"))
    assert (a.parent, a.seq) == (None, 7)
    assert (b.parent, b.seq) == ("a", 7)          # inherits the tick
    assert (c.parent, c.seq) == ("a.b", 9)
    assert (free.parent, free.seq) == (None, None)
    assert a.start_ns <= b.start_ns <= c.start_ns <= c.end_ns <= b.end_ns \
        <= a.end_ns
    assert reg.durations("a") == [a.seconds]


def test_a_span_records_when_its_block_raises(reg):
    with pytest.raises(RuntimeError):
        with reg.span("boom", seq=1):
            raise RuntimeError
    assert [r.seq for r in reg.records("boom")] == [1]
    with reg.span("after"):
        pass
    assert reg.records("after")[0].parent is None   # the stack unwound


def test_counters_add(reg):
    reg.count("x", 3)
    reg.count("x", np.int64(4))
    reg.count("y")
    assert reg.snapshot()["counters"] == {"x": 7, "y": 1}


def test_the_ring_is_bounded_and_totals_are_not(reg):
    n = telemetry.RING + 10
    for i in range(n):
        with reg.span("tick", seq=i):
            pass
    recs = reg.records("tick")
    assert len(recs) == telemetry.RING
    assert recs[0].seq == 10 and recs[-1].seq == n - 1
    assert reg.snapshot()["spans"]["tick"]["count"] == n


def test_snapshot_and_reset(reg):
    for i, d in enumerate((1, 3, 2)):
        reg._record(telemetry.Span("s", 0, d * 1_000_000, None, i))
    reg.count("c", 5)
    snap = reg.snapshot()
    s = snap["spans"]["s"]
    assert s["count"] == 3 and s["max_seq"] == 1
    assert s["total_s"] == pytest.approx(6e-3)
    assert s["max_s"] == pytest.approx(3e-3)
    assert s["p50_s"] == pytest.approx(2e-3)
    assert snap["counters"] == {"c": 5}
    reg.reset()
    assert reg.snapshot() == {"spans": {}, "counters": {}}
    assert reg.records("s") == [] and reg.durations("s") == []


def test_fleet_service_spans_and_counters():
    telemetry.reset()
    svc = FleetService(make_model(), CFG, n_slots=3, chunk_size=C,
                       backend="jnp", adc_bits=4,
                       control=CaptureConfig(hp_bits=12))
    for sid in range(3):
        svc.attach(sid)
    trace = frames(4)
    puts = []
    real_put = svc._put
    svc._put = lambda x, spec=None: (puts.append(x.nbytes),
                                     real_put(x, spec))[1]
    seqs = [svc.dispatch({0: trace[0], 1: trace[1], 2: trace[2]}),
            svc.dispatch({0: trace[3], 2: trace[1]})]    # sensor 1 absent
    chunks = [svc.collect(), svc.collect()]
    assert [c.seq for c in chunks] == seqs == [0, 1]

    for name in DISPATCH + COLLECT:
        assert [r.seq for r in telemetry.records(name)] == seqs, name
    parents = {n: {r.parent for r in telemetry.records(n)}
               for n in DISPATCH + COLLECT}
    assert parents["serve.dispatch"] == parents["serve.collect"] == {None}
    assert all(parents[n] == {"serve.dispatch"} for n in DISPATCH[1:])
    assert all(parents[n] == {"serve.collect"} for n in COLLECT[1:])

    cnt = telemetry.snapshot()["counters"]
    assert cnt["serve.h2d_bytes"] == sum(puts) > 0
    # the raw super-chunk goes up twice a tick: in dispatch, and again
    # for the HP capture
    assert puts.count(svc.n_slots * C * HW[0] * HW[1] * 4) == 2 * len(seqs)
    assert cnt["serve.arrival_frames"] == 5 * C
    assert cnt["serve.scored_frames"] == 2 * svc.n_slots * C
    assert cnt["serve.sampled_frames"] == sum(
        int(m.sum()) for c in chunks for m in c.sampled.values())
    hp = sum(svc.drain_hp(sid)[0].shape[0] for sid in range(3))
    assert cnt["serve.hp_frames"] == hp


def test_cascade_service_spans_per_batch():
    from repro import configs
    from repro.launch import steps
    from repro.launch.cascade import CascadeService

    cfg = configs.get_smoke("hubert-xlarge")
    params = steps.init_detector_params(jax.random.PRNGKey(7), cfg,
                                        frame_hw=HW, patch=8)
    casc = CascadeService(params, cfg, batch_size=2, frame_hw=HW)
    telemetry.reset()
    casc.submit("a", np.arange(5), frames(5)[:, 0])
    batches = casc.flush()
    assert len(batches) == 3
    for name in ("cascade.launch", "cascade.wait"):
        assert [r.seq for r in telemetry.records(name)] == [0, 1, 2], name
    block = 2 * HW[0] * HW[1] * 4                 # batch_size float frames
    assert telemetry.snapshot()["counters"]["cascade.h2d_bytes"] == 3 * block


def test_back_pressure_collect_is_not_inside_dispatch():
    telemetry.reset()
    svc = FleetService(make_model(), CFG, n_slots=2, chunk_size=C,
                       backend="jnp", adc_bits=4, max_inflight=1)
    svc.attach(0)
    trace = frames(3)
    for t in range(3):                        # dispatch only: back-pressure
        svc.dispatch({0: trace[t]})
    assert [r.seq for r in telemetry.records("serve.collect")] == [0, 1]
    assert {r.parent for r in telemetry.records("serve.collect")} == {None}
    assert svc.collect().seq == 0


def test_gate_step_carries_the_named_scopes():
    svc = FleetService(make_model(), CFG, n_slots=2, chunk_size=C,
                       backend="pallas", adc_bits=4, block_d=32,
                       control=CaptureConfig(hp_bits=12))
    svc.attach(0)
    svc.dispatch({0: frames(1)[0]})
    svc.flush()
    text = svc.compiled_step_text()
    for scope in ("tile_fold", "cosine_epilogue", "control_scan"):
        assert f"/{scope}/" in text, scope
