"""FleetService: slot-pooled, double-buffered, checkpointed serving.

The serving layer's contracts (repro/launch/serve.py):

* churn-free, a service collected tick by tick == the same service
  pipelined by the FleetRunner replay, bitwise, per backend;
* ANY attach/detach/ragged-arrival schedule == independent StreamRunner
  per sensor, bitwise — including adapted per-stream classifiers and
  ADC noise keyed by persistent sensor uid (property-based);
* a short tick (L < chunk_size frames per arrival) == the frame-at-a-time
  reference, and never recompiles; a tick that mixes lengths is refused;
* detach -> reattach restores a sensor's adapted classifier, gate hold,
  and capture log exactly, through intervening slot tenants;
* churn never recompiles the fleet step (fixed shapes, mask-only);
* checkpoint kill-and-resume is bitwise on both backends;
* pipelining depth (max_inflight) is invisible to results (FIFO).
"""

import os
import tempfile

try:  # prefer the real library when installed (requirements-dev.txt)
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # fallback keeps these tests running without the dep
    from _hypothesis_fallback import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoding, hypersense
from repro.core.online import AdaptConfig
from repro.core.sensor_control import (CaptureConfig, ControllerConfig,
                                       simulate_stream)
from repro.launch.mesh import make_mesh
from repro.launch.replay import FleetRunner, StreamRunner
from repro.launch.serve import FleetService
from repro.sensing import adc as adc_sim
from repro.sensing.stream import adc_view


def make_model(h=6, w=6, stride=3, D=64, t_score=-0.05, t_detection=2):
    B0, b = encoding.make_perm_base_rows(jax.random.PRNGKey(1), h, D)
    C = jax.random.normal(jax.random.PRNGKey(2), (2, D))
    return hypersense.HyperSenseModel(C, B0, b, h, w, stride,
                                      t_score=t_score,
                                      t_detection=t_detection)


def make_trace(S, N, height=18, width=18, seed=3):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(S, N, height, width)).astype(np.float32)


CFG = ControllerConfig(hold_frames=2)
C = 4   # chunk_size everywhere here


def drain(svc, got):
    for ch in svc.flush():
        for sid, out in ch.outputs.items():
            got.setdefault(sid, []).append(out)


def cat(got_sid):
    return [np.concatenate([o[j] for o in got_sid]) for j in range(3)]


# ---------------------------------------------------------------------------
# churn-free: tick-by-tick collect == the pipelined FleetRunner replay
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("kw", [
    {},
    {"adc_bits": 5, "adc_sigma": 0.02},
    {"adapt": AdaptConfig(mode="pseudo", scope="shared", lr=0.3)},
    {"control": CaptureConfig(hp_bits=12, hp_buffer=2)},
], ids=["frozen", "adc-noise", "adapt-shared", "closed-loop"])
def test_churn_free_bitwise_vs_fleet_runner(backend, kw):
    """The FleetRunner replay keeps up to max_inflight ticks in flight
    (donated state, rotating host buffers, HP capture at collect); a
    service collected after every dispatch never does. Both are bitwise
    equal: pipelining is invisible to every output, log and capture."""
    model = make_model()
    S, T = 3, 4
    trace = make_trace(S, T * C)
    runner = FleetRunner(model, CFG, chunk_size=C, backend=backend,
                         block_d=64, **kw)
    s_ref, f_ref, g_ref = runner.process(trace)

    svc = FleetService(model, CFG, n_slots=S, chunk_size=C,
                       backend=backend, block_d=64, **kw)
    for i in range(S):
        svc.attach(i)
    got = {}
    for t in range(T):
        svc.dispatch({i: trace[i, t * C:(t + 1) * C] for i in range(S)})
        ch = svc.collect()                # synchronous: nothing in flight
        for sid, out in ch.outputs.items():
            got.setdefault(sid, []).append(out)
    assert svc.collect() is None
    for i in range(S):
        s, f, g = cat(got[i])
        np.testing.assert_array_equal(s, s_ref[i])
        np.testing.assert_array_equal(f, f_ref[i])
        np.testing.assert_array_equal(g, g_ref[i])
        log = svc.capture_log(i)
        np.testing.assert_array_equal(log.sampled,
                                      runner.capture_log.sampled[i])
        np.testing.assert_array_equal(log.gated,
                                      runner.capture_log.gated[i])
    if "control" in kw:
        ref_hp = runner.drain_hp()
        for i in range(S):
            idx, frames = svc.drain_hp(i)
            np.testing.assert_array_equal(idx, ref_hp[i][0])
            np.testing.assert_array_equal(frames, ref_hp[i][1])
        assert svc.hp_dropped == runner.hp_dropped


# ---------------------------------------------------------------------------
# slot-pool churn == independent StreamRunners (property-based)
# ---------------------------------------------------------------------------

@hypothesis.given(st.integers(0, 2 ** 31 - 1), st.booleans())
@hypothesis.settings(max_examples=8, deadline=None)
def test_any_churn_schedule_matches_independent_runners(seed, adapt_on):
    """Random attach/detach/silence schedule: every sensor's served
    outputs, capture log and (with per-stream adapt) final classifier
    are bitwise an independent StreamRunner's over just its own frames —
    whatever slots it landed in, whoever shared the step with it."""
    model = make_model()
    rng = np.random.default_rng(seed)
    n_sensors, n_slots, T = 5, 3, 6
    trace = make_trace(n_sensors, T * C, seed=seed % 1000)
    adapt = (AdaptConfig(mode="pseudo", scope="per-stream", lr=0.3)
             if adapt_on else None)
    kw = dict(chunk_size=C, backend="jnp", adc_bits=5, adc_sigma=0.02)
    svc = FleetService(model, CFG, n_slots=n_slots, adapt=adapt, **kw)

    attached, fed, got = set(), {}, {}
    warm = False                # first dispatch must carry >= 1 arrival
    for t in range(T):
        # mutate membership: random attach (if capacity) / detach
        if attached and rng.random() < 0.3:
            gone = rng.choice(sorted(attached))
            svc.detach(int(gone))
            attached.discard(int(gone))
        if svc.free_slots and rng.random() < 0.7:
            cand = [i for i in range(n_sensors) if i not in attached]
            if cand:
                sid = int(rng.choice(cand))
                svc.attach(sid)
                attached.add(sid)
        # ragged arrival: each attached sensor delivers this tick or not
        arrivals = {}
        for sid in sorted(attached):
            if rng.random() < 0.8:
                n0 = fed.setdefault(sid, 0)
                arrivals[sid] = trace[sid, n0:n0 + C]
                fed[sid] = n0 + C
        if not arrivals and not warm:
            continue            # frame shape not fixed yet — no tick
        warm = True
        svc.dispatch(arrivals)
    drain(svc, got)

    base_key = jax.random.PRNGKey(0)   # FleetService's default adc_key
    for sid, n in fed.items():
        # the sensor's ADC captures: noise keyed by its persistent uid
        captured = adc_view(trace[sid, :n], 5, sigma=0.02,
                            key=jax.random.fold_in(base_key, svc.uid(sid)))
        ref = StreamRunner(
            model, CFG, chunk_size=C, backend="jnp", adc_bits=5,
            adapt=(AdaptConfig(mode="pseudo", scope="shared", lr=0.3)
                   if adapt_on else None))
        s_ref, f_ref, g_ref = ref.process(captured)
        s, f, g = cat(got[sid])
        np.testing.assert_array_equal(s, s_ref)
        np.testing.assert_array_equal(f, f_ref)
        np.testing.assert_array_equal(g, g_ref)
        log = svc.capture_log(sid)
        np.testing.assert_array_equal(log.sampled, ref.capture_log.sampled)
        np.testing.assert_array_equal(log.gated, ref.capture_log.gated)
        if adapt_on:
            np.testing.assert_array_equal(svc.class_hvs_of(sid),
                                          np.asarray(ref.class_hvs))


def test_detach_reattach_restores_adapted_classifier_exactly():
    """A detached sensor's adapted class_hvs survives an intervening
    tenant in its slot and is restored bitwise on reattach."""
    model = make_model()
    adapt = AdaptConfig(mode="pseudo", scope="per-stream", lr=0.3)
    trace = make_trace(3, 6 * C)
    svc = FleetService(model, CFG, n_slots=1, chunk_size=C, backend="jnp",
                       adapt=adapt)
    svc.attach("a")
    svc.dispatch({"a": trace[0, 0:C]})
    svc.dispatch({"a": trace[0, C:2 * C]})
    svc.flush()
    chvs_a = svc.class_hvs_of("a")
    assert not np.array_equal(chvs_a, np.asarray(model.class_hvs)), \
        "sanity: adaptation must have moved the classifier"
    svc.detach("a")
    np.testing.assert_array_equal(svc.class_hvs_of("a"), chvs_a)
    # another tenant adapts in the same slot
    svc.attach("b")
    svc.dispatch({"b": trace[1, 0:C]})
    svc.flush()
    svc.detach("b")
    # reattach: parked classifier restored bitwise, and it keeps adapting
    # exactly as an uninterrupted runner would
    svc.attach("a")
    np.testing.assert_array_equal(svc.class_hvs_of("a"), chvs_a)
    svc.dispatch({"a": trace[0, 2 * C:3 * C]})
    svc.flush()
    ref = StreamRunner(model, CFG, chunk_size=C, backend="jnp",
                       adapt=AdaptConfig(mode="pseudo", scope="shared",
                                         lr=0.3))
    ref.process(trace[0, :3 * C])
    np.testing.assert_array_equal(svc.class_hvs_of("a"),
                                  np.asarray(ref.class_hvs))


def test_churn_never_recompiles_the_step():
    model = make_model()
    trace = make_trace(4, 8 * C)
    svc = FleetService(model, CFG, n_slots=2, chunk_size=C, backend="jnp")
    svc.attach(0)
    svc.dispatch({0: trace[0, 0:C]})      # warmup fixes the trace
    svc.flush()
    c0 = svc.compile_count()
    svc.attach(1)
    svc.dispatch({0: trace[0, C:2 * C], 1: trace[1, 0:C]})
    svc.detach(0)
    svc.dispatch({1: trace[1, C:2 * C]})
    svc.dispatch({})                      # fully silent tick
    svc.attach(2)
    svc.dispatch({2: trace[2, 0:C]})
    svc.flush()
    assert svc.compile_count() == c0, \
        "slot churn must only flip slot_mask bits, never retrace"


# ---------------------------------------------------------------------------
# short ticks: L < chunk_size frames per arrival
# ---------------------------------------------------------------------------

C_LONG = 8   # a chunk long enough for L in {1, 5, C_LONG - 1}


@pytest.mark.parametrize("L", [1, 5, C_LONG - 1])
def test_short_tick_matches_frame_at_a_time_reference(L):
    """Ticks of L, C and L frames — a short first tick fixes the frame
    shape, a short tick follows a full one — give each sensor the
    frame-at-a-time reference's scores, decisions and gate, with
    outputs, logs and HP captures of exactly its delivered frames."""
    n = 2 * L + C_LONG
    trace = make_trace(2, n)
    base = make_model()
    score = jax.jit(lambda f: hypersense.frame_detection_score(
        hypersense.score_frame(base, f), base.t_detection))
    # threshold in the widest gap of the middle half of the scores: both
    # gate outcomes occur, and no score sits near the threshold
    mid = np.sort([float(score(fr)) for fr in trace.reshape(
        -1, *trace.shape[2:])])[n // 2:-(n // 2)]
    gap = int(np.argmax(np.diff(mid)))
    model = base._replace(t_score=float(mid[gap:gap + 2].mean()))
    svc = FleetService(model, CFG, n_slots=2, chunk_size=C_LONG,
                       backend="jnp",
                       control=CaptureConfig(subsample=False, hp_bits=12))
    for sid in range(2):
        svc.attach(sid)
    got = {}
    for a, z in [(0, L), (L, L + C_LONG), (L + C_LONG, n)]:
        svc.dispatch({sid: trace[sid, a:z] for sid in range(2)})
        (ch,) = svc.flush()
        for sid, out in ch.outputs.items():
            assert [o.shape for o in out] == [(z - a,)] * 3
            assert ch.sampled[sid].shape == (z - a,)
            got.setdefault(sid, []).append(out)
    decide = jax.jit(lambda f: hypersense.detect(model, f))
    for sid in range(2):
        ref = simulate_stream(lambda f: bool(decide(f)), trace[sid],
                              np.zeros(n), CFG)
        s, f, g = cat(got[sid])
        np.testing.assert_allclose(
            s, [float(score(fr)) for fr in trace[sid]], rtol=1e-5,
            atol=1e-5)
        np.testing.assert_array_equal(f, ref.decisions)
        np.testing.assert_array_equal(g, ref.gated_on)
        assert svc.capture_log(sid).sampled.shape == (n,)
        idx, hp = svc.drain_hp(sid)
        np.testing.assert_array_equal(idx, np.flatnonzero(ref.gated_on))
        np.testing.assert_array_equal(
            hp, np.asarray(adc_sim.quantize(trace[sid][idx], 12)))
    assert f.any() and not f.all(), "the gate must see both outcomes"


def test_tick_mixing_lengths_is_refused():
    model = make_model()
    trace = make_trace(2, C)
    svc = FleetService(model, CFG, n_slots=2, chunk_size=C, backend="jnp")
    svc.attach(0)
    svc.attach(1)
    with pytest.raises(ValueError, match="one length"):
        svc.dispatch({0: trace[0], 1: trace[1, :C - 1]})
    # refused before any state moved: the next tick is the streams' first
    svc.dispatch({0: trace[0], 1: trace[1]})
    (ch,) = svc.flush()
    assert ch.seq == 0
    s_ref, _, g_ref = StreamRunner(model, CFG, chunk_size=C,
                                   backend="jnp").process(trace[1])
    np.testing.assert_array_equal(ch.outputs[1][0], s_ref)
    np.testing.assert_array_equal(ch.outputs[1][2], g_ref)


def test_short_tick_never_recompiles_the_step():
    model = make_model()
    trace = make_trace(1, 3 * C)
    svc = FleetService(model, CFG, n_slots=1, chunk_size=C, backend="jnp",
                       adc_bits=5, control=CaptureConfig(hp_bits=12))
    svc.attach(0)
    svc.dispatch({0: trace[0, :C]})       # warmup fixes the trace
    svc.flush()
    c0 = svc.compile_count()
    svc.dispatch({0: trace[0, C:C + 1]})
    svc.dispatch({0: trace[0, C + 1:2 * C + 1]})
    svc.dispatch({0: trace[0, 2 * C + 1:]})
    svc.flush()
    assert svc.compile_count() == c0, \
        "the tick length is the traced n_valid operand, never a retrace"


# ---------------------------------------------------------------------------
# checkpoint / restore
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_checkpoint_kill_and_resume_bitwise(backend, tmp_path):
    """A service killed after an async snapshot and restored into a
    fresh process-equivalent resumes the trace bitwise — outputs, logs,
    adapted classifier, parked sensors, HP deliverables."""
    model = make_model()
    adapt = AdaptConfig(mode="pseudo", scope="per-stream", lr=0.3)
    ctl = CaptureConfig(hp_bits=12, hp_buffer=2)
    cfg = ControllerConfig(hold_frames=2, base_rate_hz=10.0,
                           active_rate_hz=30.0)
    trace = make_trace(2, 6 * C)
    td = os.fspath(tmp_path)

    def build():
        return FleetService(model, cfg, n_slots=2, chunk_size=C,
                            backend=backend, block_d=64, adapt=adapt,
                            adc_bits=5, adc_sigma=0.02, control=ctl,
                            ckpt_dir=td)

    svc = build()
    svc.attach("x")
    svc.attach("y")
    svc.dispatch({"x": trace[0, 0:C], "y": trace[1, 0:C]})
    svc.detach("y")                       # parked at snapshot time
    svc.dispatch({"x": trace[0, C:2 * C]})
    svc.flush()
    svc.drain_hp("x")                     # pre-snapshot HP already taken
    svc.checkpoint()
    svc.wait_ckpt()

    def continuation(s):
        s.attach("y")
        s.dispatch({"x": trace[0, 2 * C:3 * C], "y": trace[1, C:2 * C]})
        out = {}
        drain(s, out)
        return out

    ref = continuation(svc)
    svc2 = build()
    assert svc2.restore() == 2
    assert svc2.attached == ("x",)
    got = continuation(svc2)
    assert set(ref) == set(got)
    for sid in ref:
        for a, b in zip(cat(ref[sid]), cat(got[sid])):
            np.testing.assert_array_equal(a, b)
    for sid in ("x", "y"):
        np.testing.assert_array_equal(svc.class_hvs_of(sid),
                                      svc2.class_hvs_of(sid))
        for a, b in zip(
                (svc.capture_log(sid).sampled, svc.capture_log(sid).gated),
                (svc2.capture_log(sid).sampled,
                 svc2.capture_log(sid).gated)):
            np.testing.assert_array_equal(a, b)
    idx, frames = svc.drain_hp("x")       # post-snapshot captures only
    idx2, frames2 = svc2.drain_hp("x")
    np.testing.assert_array_equal(idx, idx2)
    np.testing.assert_array_equal(frames, frames2)


def test_ckpt_every_autosnapshots(tmp_path):
    from repro.ckpt import checkpoint as ckpt_mod
    model = make_model()
    trace = make_trace(1, 4 * C)
    svc = FleetService(model, CFG, n_slots=1, chunk_size=C, backend="jnp",
                       ckpt_dir=os.fspath(tmp_path), ckpt_every=2)
    svc.attach(0)
    for t in range(4):
        svc.dispatch({0: trace[0, t * C:(t + 1) * C]})
    svc.flush()
    svc.wait_ckpt()
    assert ckpt_mod.latest_step(os.fspath(tmp_path)) == 4


def test_restore_guards():
    model = make_model()
    with tempfile.TemporaryDirectory() as td:
        svc = FleetService(model, CFG, n_slots=1, chunk_size=C,
                           backend="jnp", ckpt_dir=td)
        svc.attach(0)
        svc.dispatch({0: make_trace(1, C)[0]})
        svc.flush()
        svc.checkpoint()
        svc.wait_ckpt()
        with pytest.raises(RuntimeError, match="freshly constructed"):
            svc.restore()
        other = FleetService(model, CFG, n_slots=5, chunk_size=C,
                             backend="jnp", ckpt_dir=td)
        with pytest.raises(ValueError, match="n_slots"):
            other.restore()
    with pytest.raises(RuntimeError, match="ckpt_dir"):
        FleetService(model, CFG, n_slots=1, chunk_size=C).checkpoint()
    with pytest.raises(ValueError, match="ckpt_dir"):
        FleetService(model, CFG, n_slots=1, chunk_size=C, ckpt_every=2)


# ---------------------------------------------------------------------------
# pipelining / pool mechanics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("inflight", [1, 2, 4])
def test_max_inflight_is_invisible_to_results(inflight):
    model = make_model()
    S, T = 2, 5
    trace = make_trace(S, T * C)
    ref_svc = FleetService(model, CFG, n_slots=S, chunk_size=C,
                           backend="jnp", max_inflight=2)
    svc = FleetService(model, CFG, n_slots=S, chunk_size=C,
                       backend="jnp", max_inflight=inflight)
    outs = []
    for s in (ref_svc, svc):
        for i in range(S):
            s.attach(i)
        got = {}
        seqs = []
        for t in range(T):
            s.dispatch({i: trace[i, t * C:(t + 1) * C] for i in range(S)})
        for ch in s.flush():
            seqs.append(ch.seq)
            for sid, out in ch.outputs.items():
                got.setdefault(sid, []).append(out)
        assert seqs == sorted(seqs), "collect must be FIFO"
        outs.append(got)
    for i in range(S):
        for a, b in zip(cat(outs[0][i]), cat(outs[1][i])):
            np.testing.assert_array_equal(a, b)
    assert svc.collect() is None          # drained


# sensor 2 arrives on ticks 0-2 and 6-7 only, sensor 1 skips ticks 2 and 5:
# a slot filled on tick t is empty on tick t + 2 or t + 3, which lands on
# the same host buffer when max_inflight is 1 or 2
RAGGED = [(0, 1, 2), (0, 1, 2), (0, 2), (0, 1), (0, 1), (0,), (0, 1, 2),
          (0, 1, 2)]


def _ragged_service(model, inflight, control):
    svc = FleetService(model, CFG, n_slots=3, chunk_size=C, backend="jnp",
                       adc_bits=4, adc_sigma=0.02, control=control,
                       max_inflight=inflight)
    for sid in range(3):
        svc.attach(sid)
    return svc


def _held_buffers(svc) -> list:
    bufs = list(svc._host_free) + [r.host for r in svc._pending]
    return [b for i, b in enumerate(bufs)
            if not any(b is o for o in bufs[:i])]


@pytest.mark.parametrize("capture", [False, True])
@pytest.mark.parametrize("inflight", [1, 2])
def test_reused_host_buffers_are_invisible_to_results(inflight, capture):
    """Ticks rotate through max_inflight + 1 host buffers; a slot left
    empty on a recycled buffer must read as the fresh buffer's zeros."""
    model = make_model()
    T = len(RAGGED)
    trace = make_trace(3, T * C)
    control = CaptureConfig(hp_bits=12) if capture else None
    runs = []
    for depth in (inflight, T):           # T: the pool never wraps
        svc = _ragged_service(model, depth, control)
        hosts = []
        for t, present in enumerate(RAGGED):
            svc.dispatch({i: trace[i, t * C:(t + 1) * C] for i in present})
            host = svc._pending[-1].host
            hosts.append((host, host.copy()))
            assert len(_held_buffers(svc)) <= depth + 1
        chunks = svc.flush()
        runs.append((hosts, chunks, [svc.drain_hp(i) for i in range(3)]))
    (hosts, chunks, hp), (ref_hosts, ref_chunks, ref_hp) = runs
    period = inflight + 1
    assert all(hosts[t][0] is hosts[t + period][0]
               for t in range(T - period)), "the pool must wrap"
    assert all(ref_hosts[t][0] is not ref_hosts[u][0]
               for t in range(T) for u in range(t))
    for (_, got), (_, want) in zip(hosts, ref_hosts):
        np.testing.assert_array_equal(got, want)
    assert [c.seq for c in chunks] == [c.seq for c in ref_chunks]
    for ch, ref in zip(chunks, ref_chunks):
        assert ch.outputs.keys() == ref.outputs.keys()
        for sid in ref.outputs:
            for a, b in zip(ch.outputs[sid], ref.outputs[sid]):
                np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(ch.sampled[sid], ref.sampled[sid])
    for (idx, fr), (ref_idx, ref_fr) in zip(hp, ref_hp):
        np.testing.assert_array_equal(idx, ref_idx)
        np.testing.assert_array_equal(fr, ref_fr)
    if capture:
        assert sum(i.size for i, _ in hp) > 0, "trace must capture frames"


@pytest.mark.parametrize("inflight", [1, 2])
def test_host_buffer_pool_is_bounded_and_counted(inflight):
    """At most max_inflight + 1 host buffers under back-to-back ticks,
    flush, back-pressure and churn; ``serve.assemble_reused`` counts every
    tick after the pool's first fill."""
    from repro.launch import telemetry

    model = make_model()
    T = 6
    trace = make_trace(3, 3 * T * C)
    svc = _ragged_service(model, inflight, None)
    seen = []

    def tick(t, present=(0, 1, 2)):
        svc.dispatch({i: trace[i, t * C:(t + 1) * C] for i in present})
        held = _held_buffers(svc)
        assert len(held) <= inflight + 1
        seen.extend(b for b in held if not any(b is o for o in seen))

    telemetry.reset()
    for t in range(T):                    # back to back
        tick(t)
    svc.flush()
    assert telemetry.snapshot()["counters"]["serve.assemble_reused"] == \
        T - (inflight + 1)
    assert len(_held_buffers(svc)) == inflight + 1
    for t in range(T, 2 * T):             # back-pressure, through churn
        if t == T + 1:
            svc.detach(1)
        if t == T + 3:
            svc.attach(1)
        tick(t, (0, 2) if T + 1 <= t < T + 3 else (0, 1, 2))
        if t % 3 == 0:
            assert svc.collect() is not None
    svc.flush()
    assert len(seen) == inflight + 1
    assert telemetry.snapshot()["counters"]["serve.assemble_reused"] == \
        2 * T - (inflight + 1)


def test_host_buffer_pool_follows_arrival_dtype():
    """Integer-code arrivals assemble into int32 buffers: the float pool
    is dropped, and the new pool stays within max_inflight + 1."""
    model = make_model()
    trace = make_trace(1, 6 * C)
    codes = np.clip(np.abs(trace) * 8, 0, 31).astype(np.int32)
    svc = FleetService(model, CFG, n_slots=1, chunk_size=C, backend="jnp",
                       precision="int8", adc_bits=5, max_inflight=1)
    svc.attach(0)
    for t in range(3):
        svc.dispatch({0: trace[0, t * C:(t + 1) * C]})
    for t in range(3, 6):
        svc.dispatch({0: codes[0, t * C:(t + 1) * C]})
        assert len(_held_buffers(svc)) <= 2
    svc.flush()
    held = _held_buffers(svc)
    assert len(held) == 2 and all(b.dtype == np.int32 for b in held)
    # codes carry no raw frames for an HP capture: refused at dispatch
    cap = FleetService(model, CFG, n_slots=1, chunk_size=C, backend="jnp",
                       precision="int8", adc_bits=5,
                       control=CaptureConfig(hp_bits=12))
    cap.attach(0)
    with pytest.raises(ValueError, match="raw frames"):
        cap.dispatch({0: codes[0, :C]})


def test_hp_capture_reads_each_ticks_own_frames():
    """With max_inflight=1 the pool rotates every other tick; each tick's
    HP capture must still read its own raw frames, not a later tick's."""
    model = make_model(t_score=-0.2)      # every frame passes the gate
    T = 6
    trace = make_trace(2, T * C)
    svc = FleetService(model, CFG, n_slots=2, chunk_size=C, backend="jnp",
                       adc_bits=4, control=CaptureConfig(hp_bits=12),
                       max_inflight=1)
    svc.attach(0)
    svc.attach(1)
    gated = {0: [], 1: []}
    for t in range(T):
        svc.dispatch({i: trace[i, t * C:(t + 1) * C] for i in range(2)})
    for ch in svc.flush():
        for sid, (_, _, g) in ch.outputs.items():
            gated[sid].append(g)
    hp12 = np.asarray(adc_sim.quantize_per_frame(
        jnp.asarray(trace.reshape(-1, *trace.shape[2:])),
        jnp.full((2 * T * C,), 12))).reshape(trace.shape)
    ticks = set()
    for sid in range(2):
        idx, frames = svc.drain_hp(sid)
        np.testing.assert_array_equal(
            idx, np.flatnonzero(np.concatenate(gated[sid])))
        np.testing.assert_array_equal(frames, hp12[sid, idx])
        ticks.update((idx // C).tolist())
    assert ticks == set(range(T)) and svc.hp_dropped == 0


def test_slot_pool_errors():
    model = make_model()
    svc = FleetService(model, CFG, n_slots=1, chunk_size=C, backend="jnp")
    svc.attach("a")
    with pytest.raises(ValueError, match="already attached"):
        svc.attach("a")
    with pytest.raises(RuntimeError, match="exhausted"):
        svc.attach("b")
    with pytest.raises(ValueError, match="not attached"):
        svc.detach("b")
    with pytest.raises(TypeError, match="str or int"):
        svc.attach(("tuple", "sid"))
    with pytest.raises(ValueError, match="not attached"):
        svc.dispatch({"b": make_trace(1, C)[0]})
    with pytest.raises(ValueError, match="expected"):
        svc.dispatch({"a": make_trace(1, C + 1)[0]})
    with pytest.raises(ValueError, match="labels"):
        svc.dispatch({"a": make_trace(1, C)[0]},
                     labels={"a": np.zeros(C, np.int32)})
    with pytest.raises(ValueError, match="n_slots"):
        FleetService(model, CFG, n_slots=0, chunk_size=C)
    with pytest.raises(ValueError, match="max_inflight"):
        FleetService(model, CFG, n_slots=1, chunk_size=C, max_inflight=0)


def test_detach_frees_capacity_for_new_tenant():
    model = make_model()
    trace = make_trace(2, 2 * C)
    svc = FleetService(model, CFG, n_slots=1, chunk_size=C, backend="jnp")
    svc.attach("a")
    svc.dispatch({"a": trace[0, 0:C]})
    svc.detach("a")
    assert svc.free_slots == 1
    svc.attach("b")                       # reuses the slot
    svc.dispatch({"b": trace[1, 0:C]})
    got = {}
    drain(svc, got)
    # b's outputs are a fresh stream's, not a continuation of a's
    ref = StreamRunner(model, CFG, chunk_size=C, backend="jnp")
    s_ref, f_ref, g_ref = ref.process(trace[1, 0:C])
    np.testing.assert_array_equal(cat(got["b"])[0], s_ref)
    np.testing.assert_array_equal(cat(got["b"])[2], g_ref)
    # a's uid persists while parked
    assert svc.uid("a") != svc.uid("b")


# ---------------------------------------------------------------------------
# mesh-sharded service (8-device host mesh jobs only)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8")
def test_mesh_sharded_service_matches_unsharded():
    """n_slots pads up to the mesh "sensors" extent and the sharded
    service's churn trace is bitwise the unsharded one."""
    from repro.distributed import sharding as shlib
    model = make_model()
    trace = make_trace(3, 4 * C)
    mesh = make_mesh((8, 1), ("data", "model"))

    def play(svc):
        svc.attach(0)
        svc.dispatch({0: trace[0, 0:C]})
        svc.attach(1)
        svc.dispatch({0: trace[0, C:2 * C], 1: trace[1, 0:C]})
        svc.detach(0)
        svc.dispatch({1: trace[1, C:2 * C]})
        got = {}
        drain(svc, got)
        return got

    with shlib.use_mesh(mesh):
        sharded = FleetService(model, CFG, n_slots=3, chunk_size=C,
                               backend="jnp", adc_bits=5, adc_sigma=0.02)
        assert sharded.n_slots == 8, "capacity must pad to the mesh extent"
        got = play(sharded)
    ref = play(FleetService(model, CFG, n_slots=3, chunk_size=C,
                            backend="jnp", adc_bits=5, adc_sigma=0.02))
    assert set(got) == set(ref)
    for sid in ref:
        for a, b in zip(cat(got[sid]), cat(ref[sid])):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# sanitizer-harness regressions (repro.analysis.sanitize)
# ---------------------------------------------------------------------------

from repro.analysis import sanitize  # noqa: E402
from repro.launch import serve as serve_mod  # noqa: E402


def test_warm_dispatch_is_compile_and_transfer_clean(compile_ledger):
    """Pins the fixes behind lint findings RA003/RA005 in ``dispatch()``.

    After warmup, serving ticks on the uid-keyed ADC path — whose
    per-tick ``jax.vmap`` used to rebuild a fresh trace every call, and
    whose shape probe used to round-trip the first arrival through
    ``np.asarray`` — must trigger ZERO fresh XLA compiles and no
    implicit host<->device transfers, even for device-array arrivals.
    """
    model = make_model()
    trace = make_trace(2, 6 * C)
    svc = FleetService(model, CFG, n_slots=2, chunk_size=C, backend="jnp",
                       adc_bits=5, adc_sigma=0.02)
    svc.attach(0)
    svc.attach(1)
    svc.dispatch({0: trace[0, :C], 1: trace[1, :C]})     # warmup compiles
    svc.flush()
    dev = jax.device_put(trace[0, C:2 * C])              # device arrival
    with compile_ledger.expect_no_compiles("warm dispatch ticks"), \
            sanitize.no_implicit_transfers(always=True):
        svc.dispatch({0: dev, 1: trace[1, C:2 * C]})
        svc.dispatch({0: trace[0, 2 * C:3 * C]})
    assert svc.flush(), "guarded ticks must still produce results"


def test_uid_key_fold_is_hoisted_not_per_tick():
    """The ADC key fold is one module-level jit, reused across ticks."""
    model = make_model()
    trace = make_trace(1, 8 * C)
    svc = FleetService(model, CFG, n_slots=1, chunk_size=C, backend="jnp",
                       adc_bits=5, adc_sigma=0.02)
    svc.attach(0)
    svc.dispatch({0: trace[0, :C]})          # first tick traces the fold
    after_first = serve_mod._fold_uid_keys._cache_size()
    for t in range(1, 4):
        svc.dispatch({0: trace[0, t * C:(t + 1) * C]})
    svc.flush()
    assert serve_mod._fold_uid_keys._cache_size() == after_first, \
        "per-tick key folding must reuse one jitted trace per fleet shape"


def test_device_arrivals_bitwise_match_host_arrivals():
    """``np.shape``/``np.result_type`` probes see device and host arrivals
    identically — same outputs bitwise, including int-codes detection."""
    model = make_model()
    trace = make_trace(1, 2 * C)
    codes = np.clip(np.abs(trace) * 8, 0, 31).astype(np.int32)

    def play(arrival_of):
        svc = FleetService(model, CFG, n_slots=1, chunk_size=C,
                           backend="jnp", precision="int8", adc_bits=5)
        svc.attach(0)
        for t in range(2):
            svc.dispatch({0: arrival_of(codes[0, t * C:(t + 1) * C])})
        got = {}
        drain(svc, got)
        return got

    host = play(lambda a: a)
    dev = play(jax.device_put)
    for a, b in zip(cat(host[0]), cat(dev[0])):
        np.testing.assert_array_equal(a, b)
