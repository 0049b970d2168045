"""FleetService: slot-pooled, double-buffered, checkpointed serving.

The serving layer's contracts (repro/launch/serve.py):

* churn-free service == synchronous FleetRunner, bitwise, per backend;
* ANY attach/detach/ragged-arrival schedule == independent StreamRunner
  per sensor, bitwise — including adapted per-stream classifiers and
  ADC noise keyed by persistent sensor uid (property-based);
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
from repro.core.sensor_control import CaptureConfig, ControllerConfig
from repro.launch.mesh import make_mesh
from repro.launch.serve import FleetService
from repro.sensing.fleet import FleetRunner
from repro.sensing.stream import StreamRunner


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
# churn-free == FleetRunner, bitwise, per backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("kw", [
    {},
    {"adc_bits": 5, "adc_sigma": 0.02},
    {"adapt": AdaptConfig(mode="pseudo", scope="shared", lr=0.3)},
    {"control": CaptureConfig(hp_bits=12, hp_buffer=2)},
], ids=["frozen", "adc-noise", "adapt-shared", "closed-loop"])
def test_churn_free_bitwise_vs_fleet_runner(backend, kw):
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
    drain(svc, got)
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
        ref = StreamRunner(
            model, CFG,
            adapt=(AdaptConfig(mode="pseudo", scope="shared", lr=0.3)
                   if adapt_on else None),
            adc_key=jax.random.fold_in(base_key, svc.uid(sid)), **kw)
        s_ref, f_ref, g_ref = ref.process(trace[sid, :n])
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
