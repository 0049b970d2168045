"""Online-learning runtime: kernels-to-fleet mutable model state (ISSUE 3).

Contracts:

* the chunked online path — ``online.chunk_update`` folded chunk-by-chunk,
  and the adaptive runners built on it — reproduces ``retrain_epoch`` over
  the same sample sequence *exactly*, for any chunk size, on both
  backends;
* ``adapt=None`` runners stay bitwise-identical to the frozen pipeline
  (batched kernel scoring + ``gate_scan``) on the pallas backend;
* installing a new classifier is ``retile_classes`` (bitwise-equal to the
  host ``precompute_tiles``) and the runners' tile caches are keyed on
  class-hv *identity* — a mutated model can never score via stale tiles;
* the fleet's per-stream adaptation (one launch, stream-indexed class
  tiles) matches S independent adaptive runners.
"""

try:  # prefer the real library when installed (requirements-dev.txt)
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # fallback keeps these tests running without the dep
    from _hypothesis_fallback import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoding, fragment_model as fm, hypersense, online
from repro.core.online import AdaptConfig
from repro.core.sensor_control import ControllerConfig
from repro.kernels import ops as kops
from repro.kernels import sliding_scores as k_ss
from repro.launch.mesh import make_mesh
from repro.sensing import synthetic
from repro.sensing.fleet import FleetRunner
from repro.sensing.stream import (StreamRunner, _top_fragment_hvs,
                                  gate_scan, model_geometry)

jax.config.update("jax_platform_name", "cpu")


def key(i):
    return jax.random.PRNGKey(i)


def make_model(h=6, w=6, stride=3, D=128, t_score=-0.05, t_detection=2):
    B0, b = encoding.make_perm_base_rows(key(1), h, D)
    C = jax.random.normal(key(2), (2, D))
    return hypersense.HyperSenseModel(C, B0, b, h, w, stride,
                                      t_score=t_score,
                                      t_detection=t_detection)


def make_fleet(S, N, seed=10, height=24, width=24):
    cfg = synthetic.RadarConfig(height=height, width=width)
    frames, labels = [], []
    for s in range(S):
        f, _, y = synthetic.make_dataset(key(seed + s), N, cfg)
        frames.append(f)
        labels.append(np.asarray(y))
    return jnp.stack(frames), np.stack(labels)


# ---------------------------------------------------------------------------
# core rule: chunked online path == retrain_epoch
# ---------------------------------------------------------------------------

def test_online_update_is_retrain_step():
    hvs = jax.random.normal(key(0), (1, 64))
    chvs = jax.random.normal(key(1), (2, 64))
    y = jnp.array(1)
    got, _ = online.online_update(chvs, hvs[0], y, 0.7)
    want = fm.retrain_epoch(chvs, hvs, y[None], 0.7)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@hypothesis.given(st.integers(0, 2**16), st.integers(1, 60))
@hypothesis.settings(max_examples=15, deadline=None)
def test_chunked_online_equals_retrain_epoch_property(seed, chunk_size):
    """Folding chunk_update over ANY chunking of a sample sequence is
    bitwise the single retrain_epoch pass (the running-state property)."""
    k = key(seed)
    n = 37
    hvs = jax.random.normal(k, (n, 64))
    labels = jax.random.randint(jax.random.fold_in(k, 1), (n,), 0, 2)
    chvs0 = jax.random.normal(jax.random.fold_in(k, 2), (2, 64))
    want = fm.retrain_epoch(chvs0, hvs, labels, 0.8)
    chvs = chvs0
    for a in range(0, n, chunk_size):
        chvs, _ = online.chunk_update(chvs, hvs[a:a + chunk_size],
                                      labels[a:a + chunk_size], lr=0.8)
    np.testing.assert_array_equal(np.asarray(chvs), np.asarray(want))


def test_chunk_update_valid_mask_is_exact_noop():
    """Masked (padded-tail) samples leave the state bitwise untouched."""
    hvs = jax.random.normal(key(3), (10, 64))
    labels = jax.random.randint(key(4), (10,), 0, 2)
    chvs0 = jax.random.normal(key(5), (2, 64))
    want, _ = online.chunk_update(chvs0, hvs[:7], labels[:7])
    valid = jnp.arange(10) < 7
    got, wrong = online.chunk_update(chvs0, hvs, labels, valid=valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not bool(np.asarray(wrong)[7:].any())


def test_pseudo_update_confidence_gate():
    hvs = jax.random.normal(key(6), (20, 64))
    chvs0 = jax.random.normal(key(7), (2, 64))
    # impossible confidence -> bitwise no-op
    same, did = online.chunk_update_pseudo(chvs0, hvs, confidence=10.0)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(chvs0))
    assert not bool(np.asarray(did).any())
    # zero confidence -> every sample reinforces its predicted class
    moved, did = online.chunk_update_pseudo(chvs0, hvs, confidence=0.0)
    assert bool(np.asarray(did).all())
    assert not np.array_equal(np.asarray(moved), np.asarray(chvs0))


def test_apply_chunk_rejects_unknown_mode():
    with pytest.raises(ValueError):
        online.apply_chunk(AdaptConfig(mode="nope"),
                           jnp.zeros((2, 8)), jnp.zeros((1, 8)),
                           jnp.zeros(1, jnp.int32))


# ---------------------------------------------------------------------------
# kernel precompute split: geometry + retile
# ---------------------------------------------------------------------------

def test_retile_matches_precompute_tiles_bitwise():
    m = make_model()
    W = 24
    tiles = kops.precompute_tiles(m.B0, m.b, m.class_hvs, W=W, w=m.w,
                                  stride=m.stride, block_d=64)
    geom = kops.precompute_geometry(m.B0, m.b, W=W, w=m.w,
                                    stride=m.stride, block_d=64)
    got = kops.retile_classes(geom, m.class_hvs)
    for f in ("cpos_t", "cneg_t", "cpos_norm", "cneg_norm"):
        np.testing.assert_array_equal(np.asarray(getattr(tiles, f)),
                                      np.asarray(getattr(got, f)))
    np.testing.assert_array_equal(np.asarray(tiles.slabs),
                                  np.asarray(got.slabs))
    np.testing.assert_array_equal(np.asarray(tiles.bias_t),
                                  np.asarray(got.bias_t))


def test_per_stream_tiles_single_launch_matches_per_classifier():
    """(S, n_dt, mx, TD) class tiles + frames_per_stream: one launch,
    bitwise equal to separate launches per classifier."""
    m = make_model()
    W, C_frames = 24, 2
    geom = model_geometry(m, W, 64)
    chvs2 = jax.random.normal(key(8), (2, 128))
    frames = jax.random.uniform(key(9), (4, W, W))
    ps = k_ss.retile_classes_fleet(geom, jnp.stack([m.class_hvs, chvs2]))
    got = k_ss.fragment_scores_batch(frames, ps, h=m.h, w=m.w,
                                     stride=m.stride, interpret=True,
                                     frames_per_stream=C_frames)
    want = jnp.concatenate([
        k_ss.fragment_scores_batch(frames[:2],
                                   k_ss.retile_classes(geom, m.class_hvs),
                                   h=m.h, w=m.w, stride=m.stride,
                                   interpret=True),
        k_ss.fragment_scores_batch(frames[2:],
                                   k_ss.retile_classes(geom, chvs2),
                                   h=m.h, w=m.w, stride=m.stride,
                                   interpret=True)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_per_stream_tiles_validates_batch_factorization():
    m = make_model()
    geom = model_geometry(m, 24, 64)
    ps = k_ss.retile_classes_fleet(geom, jnp.stack([m.class_hvs] * 2))
    frames = jax.random.uniform(key(9), (4, 24, 24))
    with pytest.raises(ValueError):
        k_ss.fragment_scores_batch(frames, ps, h=m.h, w=m.w,
                                   stride=m.stride, interpret=True)
    with pytest.raises(ValueError):
        k_ss.fragment_scores_batch(frames, ps, h=m.h, w=m.w,
                                   stride=m.stride, interpret=True,
                                   frames_per_stream=3)


# ---------------------------------------------------------------------------
# frozen path: adapt=None is the pre-refactor pipeline, bitwise (pallas)
# ---------------------------------------------------------------------------

def test_frozen_runner_bitwise_matches_direct_kernel_pipeline():
    """StreamRunner(adapt=None, backend="pallas") == hand-rolled frozen
    pipeline: host tiles -> fragment_scores_batch per chunk ->
    frame_detection_score -> threshold -> gate_scan. Bitwise."""
    m = make_model()
    frames, _ = make_fleet(S=1, N=19)
    frames = frames[0]
    r = StreamRunner(m, ControllerConfig(hold_frames=2), chunk_size=8,
                     backend="pallas", block_d=64)
    s_got, f_got, g_got = r.process(frames)

    tiles = kops.precompute_tiles(m.B0, m.b, m.class_hvs, W=24, w=m.w,
                                  stride=m.stride, block_d=64)
    s_ref, f_ref = [], []
    for a in range(0, 19, 8):
        chunk = frames[a:a + 8]
        n_valid = chunk.shape[0]
        if n_valid < 8:
            chunk = jnp.pad(chunk, ((0, 8 - n_valid), (0, 0), (0, 0)))
        maps = k_ss.fragment_scores_batch(chunk, tiles, h=m.h, w=m.w,
                                          stride=m.stride, interpret=True)
        s = jax.vmap(lambda mp: hypersense.frame_detection_score(
            mp, m.t_detection))(maps)[:n_valid]
        s_ref.append(np.asarray(s))
        f_ref.append(np.asarray(s) > m.t_score)
    s_ref = np.concatenate(s_ref)
    f_ref = np.concatenate(f_ref)
    g_ref, _ = gate_scan(jnp.asarray(f_ref), 2)
    np.testing.assert_array_equal(s_got, s_ref)
    np.testing.assert_array_equal(f_got, f_ref)
    np.testing.assert_array_equal(g_got, np.asarray(g_ref))


# ---------------------------------------------------------------------------
# adaptive runners == manual chunk-start-score + retrain-rule fold
# ---------------------------------------------------------------------------

def _manual_adaptive(m, frames, labels, chunk_size, backend, lr):
    """Reference: score each chunk with its chunk-start classifier, fold
    the top-fragment HVs through the retrain rule (== retrain_epoch over
    the extracted sample sequence)."""
    chvs = m.class_hvs
    scores = []
    n = frames.shape[0]
    mx = encoding.num_windows(frames.shape[-1], m.w, m.stride)
    for a in range(0, n, chunk_size):
        ch = frames[a:a + chunk_size]
        maps = jnp.stack([hypersense.fragment_score_map(
            f, chvs, m.B0, m.b, h=m.h, w=m.w, stride=m.stride,
            backend=backend) for f in ch])
        scores.append(np.asarray(jax.vmap(
            lambda mp: hypersense.frame_detection_score(
                mp, m.t_detection))(maps)))
        hv = _top_fragment_hvs(ch[None], maps[None], m.B0, m.b, h=m.h,
                               w=m.w, stride=m.stride, mx=mx,
                               nonlinearity=m.nonlinearity)[0]
        chvs = fm.retrain_epoch(chvs, hv,
                                jnp.asarray(labels[a:a + chunk_size]), lr)
    return np.concatenate(scores), np.asarray(chvs)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("chunk_size", [1, 4, 16])
def test_adaptive_runner_equals_retrain_fold(backend, chunk_size):
    """The chunked online path == retrain_epoch over the same extracted
    sample sequence — any chunk size, both backends (ISSUE 3 property)."""
    m = make_model()
    frames, labels = make_fleet(S=1, N=13)
    frames, labels = frames[0], labels[0]
    r = StreamRunner(m, ControllerConfig(hold_frames=2),
                     chunk_size=chunk_size, backend=backend, block_d=64,
                     adapt=AdaptConfig(mode="label", lr=0.4))
    s_got, _, _ = r.process(frames, labels=labels)
    s_want, chvs_want = _manual_adaptive(m, frames, labels, chunk_size,
                                         backend, 0.4)
    np.testing.assert_allclose(s_got, s_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r.class_hvs), chvs_want,
                               rtol=1e-4, atol=1e-4)


def test_adaptive_runner_slicing_invariance():
    """Chunk boundaries are fixed by chunk_size and the carried state, so
    re-slicing process() calls must not change the learning trajectory
    when the slices align with chunk boundaries."""
    m = make_model()
    frames, labels = make_fleet(S=1, N=16)
    frames, labels = frames[0], labels[0]
    ad = AdaptConfig(mode="label", lr=0.4)
    whole = StreamRunner(m, ControllerConfig(hold_frames=2), chunk_size=4,
                         adapt=ad)
    s_all, _, _ = whole.process(frames, labels=labels)
    split = StreamRunner(m, ControllerConfig(hold_frames=2), chunk_size=4,
                         adapt=ad)
    parts = [split.process(frames[a:z], labels=labels[a:z])
             for a, z in [(0, 4), (4, 12), (12, 16)]]
    np.testing.assert_allclose(np.concatenate([p[0] for p in parts]),
                               s_all, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(whole.class_hvs),
                                  np.asarray(split.class_hvs))


def test_stream_runner_rejects_per_stream_scope():
    with pytest.raises(ValueError):
        StreamRunner(make_model(),
                     adapt=AdaptConfig(mode="label", scope="per-stream"))


def test_adaptive_runner_requires_labels():
    m = make_model()
    r = StreamRunner(m, adapt=AdaptConfig(mode="label"))
    with pytest.raises(ValueError):
        r.process(jnp.zeros((4, 24, 24)))
    fr = FleetRunner(m, adapt=AdaptConfig(mode="label"))
    with pytest.raises(ValueError):
        fr.process(jnp.zeros((2, 4, 24, 24)))
    with pytest.raises(ValueError):       # wrong label shape
        fr.process(jnp.zeros((2, 4, 24, 24)), labels=np.zeros((2, 3)))


def test_stream_state_frame_idx_advances():
    m = make_model()
    r = StreamRunner(m, ControllerConfig(hold_frames=2), chunk_size=4)
    frames, _ = make_fleet(S=1, N=11)
    r.process(frames[0])
    assert int(np.asarray(r._state.frame_idx)) == 11


# ---------------------------------------------------------------------------
# fleet adaptation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fleet_per_stream_adapt_equals_independent_runners(backend):
    """Per-stream fleet adaptation (ONE launch, stream-indexed class
    tiles) == S independent adaptive StreamRunners."""
    m = make_model()
    frames, labels = make_fleet(S=3, N=13)
    fr = FleetRunner(m, ControllerConfig(hold_frames=2), chunk_size=4,
                     backend=backend, block_d=64,
                     adapt=AdaptConfig(mode="label", lr=0.3,
                                       scope="per-stream"))
    s_f, f_f, g_f = fr.process(frames, labels=labels)
    assert fr.class_hvs.shape == (3, 2, 128)
    for s in range(3):
        r = StreamRunner(m, ControllerConfig(hold_frames=2), chunk_size=4,
                         backend=backend, block_d=64,
                         adapt=AdaptConfig(mode="label", lr=0.3))
        s_i, f_i, g_i = r.process(frames[s], labels=labels[s])
        np.testing.assert_allclose(s_f[s], s_i, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(fr.class_hvs)[s],
                                   np.asarray(r.class_hvs),
                                   rtol=1e-4, atol=1e-4)


def test_fleet_shared_adapt_folds_time_ordered():
    """Shared-scope fleet: ONE classifier, samples folded in time order
    (stream index breaks ties) == retrain_epoch over that ordering."""
    m = make_model()
    S, N, cs = 2, 8, 4
    frames, labels = make_fleet(S=S, N=N)
    fr = FleetRunner(m, ControllerConfig(hold_frames=2), chunk_size=cs,
                     adapt=AdaptConfig(mode="label", lr=0.4,
                                       scope="shared"))
    fr.process(frames, labels=labels)

    chvs = m.class_hvs
    mx = encoding.num_windows(frames.shape[-1], m.w, m.stride)
    for a in range(0, N, cs):
        ch = frames[:, a:a + cs]
        maps = jnp.stack([jnp.stack([hypersense.fragment_score_map(
            f, chvs, m.B0, m.b, h=m.h, w=m.w, stride=m.stride)
            for f in ch[s]]) for s in range(S)])
        hv = _top_fragment_hvs(ch, maps, m.B0, m.b, h=m.h, w=m.w,
                               stride=m.stride, mx=mx,
                               nonlinearity=m.nonlinearity)     # (S, C, D)
        c = ch.shape[1]
        hv_t = jnp.transpose(hv, (1, 0, 2)).reshape(c * S, -1)
        lab_t = jnp.asarray(labels[:, a:a + cs]).T.reshape(c * S)
        chvs = fm.retrain_epoch(chvs, hv_t, lab_t, 0.4)
    np.testing.assert_allclose(np.asarray(fr.class_hvs), np.asarray(chvs),
                               rtol=1e-4, atol=1e-4)


def test_fleet_shared_adapt_sharded_folds_time_ordered():
    """Shared-scope fleet UNDER a sensor mesh: the all-gathered fold —
    not a host fallback — still equals retrain_epoch over the global
    time-ordered sequence, with a non-divisible S exercising masked pad
    slots. The sharded run is also bitwise-equal to the unsharded one."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    from repro.distributed import sharding as shlib

    m = make_model()
    S, N, cs = 3, 8, 4                         # S=3 never divides >=2 devs
    frames, labels = make_fleet(S=S, N=N)

    def run(mesh):
        fr = FleetRunner(m, ControllerConfig(hold_frames=2), chunk_size=cs,
                         adapt=AdaptConfig(mode="label", lr=0.4,
                                           scope="shared"))
        if mesh is None:
            fr.process(frames, labels=labels)
        else:
            with shlib.use_mesh(mesh):
                fr.process(frames, labels=labels)
        return fr

    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    fr = run(mesh)
    # the step really sharded: no shared-scope fallback survives
    assert fr._step_key[1] == ("data",)
    np.testing.assert_array_equal(np.asarray(fr.class_hvs),
                                  np.asarray(run(None).class_hvs))

    chvs = m.class_hvs
    mx = encoding.num_windows(frames.shape[-1], m.w, m.stride)
    for a in range(0, N, cs):
        ch = frames[:, a:a + cs]
        maps = jnp.stack([jnp.stack([hypersense.fragment_score_map(
            f, chvs, m.B0, m.b, h=m.h, w=m.w, stride=m.stride)
            for f in ch[s]]) for s in range(S)])
        hv = _top_fragment_hvs(ch, maps, m.B0, m.b, h=m.h, w=m.w,
                               stride=m.stride, mx=mx,
                               nonlinearity=m.nonlinearity)     # (S, C, D)
        c = ch.shape[1]
        hv_t = jnp.transpose(hv, (1, 0, 2)).reshape(c * S, -1)
        lab_t = jnp.asarray(labels[:, a:a + cs]).T.reshape(c * S)
        chvs = fm.retrain_epoch(chvs, hv_t, lab_t, 0.4)
    np.testing.assert_allclose(np.asarray(fr.class_hvs), np.asarray(chvs),
                               rtol=1e-4, atol=1e-4)


def test_chunk_update_interleaved_mask_is_exact_noop():
    """Pad-slot samples land INTERLEAVED in the time-major fold (every
    frame contributes one sample per padded stream slot), not just at the
    tail — masked anywhere, they must leave the fold bitwise on the
    no-pad trajectory."""
    hvs = jax.random.normal(key(6), (12, 64))
    labels = jax.random.randint(key(7), (12,), 0, 2)
    chvs0 = jax.random.normal(key(8), (2, 64))
    keep = jnp.asarray([True, True, False, True, True, False,
                        True, True, False, True, True, False])
    want, _ = online.chunk_update(chvs0, hvs[keep], labels[keep])
    got, wrong = online.chunk_update(chvs0, hvs, labels, valid=keep)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not bool(np.asarray(wrong)[~np.asarray(keep)].any())


def test_fleet_frozen_still_bitwise_after_refactor():
    """adapt=None fleet: still bitwise equal per-stream to frozen
    StreamRunners on pallas (the ISSUE 2 contract survives ISSUE 3)."""
    m = make_model()
    frames, _ = make_fleet(S=3, N=9)
    fr = FleetRunner(m, ControllerConfig(hold_frames=1), chunk_size=4,
                     backend="pallas", block_d=64)
    s_f, _, _ = fr.process(frames)
    for s in range(3):
        r = StreamRunner(m, ControllerConfig(hold_frames=1), chunk_size=4,
                         backend="pallas", block_d=64)
        s_i, _, _ = r.process(frames[s])
        np.testing.assert_array_equal(s_f[s], s_i)


# ---------------------------------------------------------------------------
# tile-cache identity keying (stale-precompute impossibility)
# ---------------------------------------------------------------------------

def test_set_class_hvs_refreshes_tiles_mid_stream():
    m = make_model()
    frames, _ = make_fleet(S=1, N=8)
    frames = frames[0]
    r = StreamRunner(m, ControllerConfig(hold_frames=2), chunk_size=4,
                     backend="pallas", block_d=64)
    s_before, _, _ = r.process(frames)
    chvs2 = jax.random.normal(key(30), (2, 128))
    r.set_class_hvs(chvs2)
    s_after, _, _ = r.process(frames)
    fresh = StreamRunner(m._replace(class_hvs=chvs2),
                         ControllerConfig(hold_frames=2), chunk_size=4,
                         backend="pallas", block_d=64)
    s_fresh, _, _ = fresh.process(frames)
    np.testing.assert_array_equal(s_after, s_fresh)
    assert not np.array_equal(s_before, s_after)


def test_fleet_set_class_hvs_refreshes_tiles_mid_stream():
    m = make_model()
    frames, _ = make_fleet(S=2, N=8)
    fr = FleetRunner(m, ControllerConfig(hold_frames=2), chunk_size=4,
                     backend="pallas", block_d=64)
    fr.process(frames)
    chvs2 = jax.random.normal(key(31), (2, 128))
    fr.set_class_hvs(chvs2)
    s_after, _, _ = fr.process(frames)
    fresh = FleetRunner(m._replace(class_hvs=chvs2),
                        ControllerConfig(hold_frames=2), chunk_size=4,
                        backend="pallas", block_d=64)
    s_fresh, _, _ = fresh.process(frames)
    np.testing.assert_array_equal(s_after, s_fresh)


def test_fleet_set_per_stream_class_hvs_before_first_process():
    """An (S, 2, D) classifier installed before any process() call must
    be honored (not silently replaced by the model's on first chunk)."""
    m = make_model()
    frames, labels = make_fleet(S=2, N=8)
    ad = AdaptConfig(mode="label", lr=0.0, scope="per-stream")
    chvs = jax.random.normal(key(32), (2, 2, 128))
    fr = FleetRunner(m, ControllerConfig(hold_frames=2), chunk_size=4,
                     adapt=ad)
    fr.set_class_hvs(chvs)
    s_got, _, _ = fr.process(frames, labels=labels)
    for s in range(2):
        r = StreamRunner(m._replace(class_hvs=chvs[s]),
                         ControllerConfig(hold_frames=2), chunk_size=4)
        s_i, _, _ = r.process(frames[s])
        np.testing.assert_allclose(s_got[s], s_i, rtol=1e-5, atol=1e-5)
    # ...and a per-stream stack without per-stream scope is rejected
    with pytest.raises(ValueError):
        FleetRunner(m, adapt=AdaptConfig(mode="label")).set_class_hvs(chvs)


def test_frozen_tile_cache_does_not_churn():
    """adapt=None: repeated process() calls must reuse the cached tiles
    object (identity key stable across chunks)."""
    m = make_model()
    frames, _ = make_fleet(S=1, N=8)
    r = StreamRunner(m, ControllerConfig(hold_frames=2), chunk_size=4,
                     backend="pallas", block_d=64)
    r.process(frames[0])
    first = r._tiles
    r.process(frames[0])
    assert r._tiles is first


# ---------------------------------------------------------------------------
# drift generators
# ---------------------------------------------------------------------------

def test_drift_stream_shapes_and_schedules():
    cfg = synthetic.RadarConfig(height=24, width=24)
    drift = synthetic.DriftConfig(background_gain=(0.0, 0.5),
                                  noise_sigma=(0.1, 0.3),
                                  object_intensity=(0.8, 0.4))
    frames, labels = synthetic.make_drift_stream(key(40), 60, cfg, drift,
                                                 event_prob=0.1,
                                                 event_len=5)
    assert frames.shape == (60, 24, 24)
    assert labels.shape == (60,)
    sched = synthetic.drift_schedule(60, (0.0, 0.5))
    assert sched[0] == 0.0 and sched[-1] == pytest.approx(0.5)
    # the background-gain ramp must show up: late background >> early
    f = np.asarray(frames)
    y = np.asarray(labels).astype(bool)
    early = f[:20][~y[:20]].mean() if (~y[:20]).any() else f[:20].mean()
    late = f[-20:][~y[-20:]].mean() if (~y[-20:]).any() else f[-20:].mean()
    assert late > early + 0.2


def test_drift_stream_defaults_match_make_stream_stats():
    """Default DriftConfig = no drift: same generator statistics as
    make_stream (same event machinery, same speckle law)."""
    cfg = synthetic.RadarConfig(height=64, width=64, noise_sigma=0.3)
    a, la = synthetic.make_drift_stream(key(41), 50, cfg)
    b, lb = synthetic.make_stream(key(41), 50, cfg)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# satellite: detect_batch via the batched scorer; top_k order statistic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_detect_batch_matches_per_frame_detect(backend):
    m = make_model(t_detection=1)
    frames, _ = make_fleet(S=1, N=7)
    frames = frames[0]
    got = hypersense.detect_batch(m, frames, backend=backend)
    want = jnp.stack([hypersense.detect(m, f, backend=backend)
                      for f in frames])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_detect_batch_t_detection_beyond_fragments_never_fires():
    m = make_model(t_detection=10_000)
    frames, _ = make_fleet(S=1, N=5)
    got = hypersense.detect_batch(m, frames[0])
    assert not bool(np.asarray(got).any())


@hypothesis.given(st.integers(0, 2**16), st.integers(0, 40))
@hypothesis.settings(max_examples=25, deadline=None)
def test_frame_detection_score_topk_equals_sort(seed, td):
    """lax.top_k path == the full-sort definition, any t_detection."""
    rng = np.random.RandomState(seed)
    scores = jnp.asarray(rng.randn(5, 6).astype(np.float32))
    flat = np.sort(np.asarray(scores).ravel())[::-1]
    k = min(td, flat.size - 1)
    got = hypersense.frame_detection_score(scores, td)
    assert float(got) == float(flat[k])
