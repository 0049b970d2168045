"""Fleet streaming runtime: S-stream batched path == S independent runners.

The contract: ``FleetRunner`` over S streams returns
per-stream results/``StreamStats`` identical to S independent
``StreamRunner`` instances — on both the ``jnp`` and ``pallas`` backends,
with and without the ADC in the loop, and unchanged under sensor-axis
sharding (``shard_map`` no-ops to the same numbers on one device; the CI
multi-device job runs the same tests on a real 8-device host mesh).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoding, energy, hypersense
from repro.core.sensor_control import ControllerConfig
from repro.distributed import sharding as shlib
from repro.launch.mesh import make_mesh
from repro.launch.replay import (FleetRunner, StreamRunner, simulate_fleet,
                                 simulate_stream_batched)
from repro.sensing import adc, synthetic
from repro.sensing.fleet import fleet_report
from repro.sensing.stream import adc_view

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


def assert_streams_equal(fleet_out, per_stream_outs):
    s_f, f_f, g_f = fleet_out
    for s, (s_i, f_i, g_i) in enumerate(per_stream_outs):
        np.testing.assert_allclose(s_f[s], s_i, rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(f_f[s], f_i)
        np.testing.assert_array_equal(g_f[s], g_i)


# ---------------------------------------------------------------------------
# fleet == S independent StreamRunners
#
# (the backend-parametrized fleet==independent-runners and the pallas
# bitwise-parity tests moved into the backend x precision x adapt matrix:
# tests/test_parity_matrix.py. What stays here is the StreamStats
# derivation, which the matrix does not cover.)
# ---------------------------------------------------------------------------

def test_fleet_stats_match_independent_simulations():
    model = make_model()
    frames, labels = make_fleet(S=4, N=21)
    cfg = ControllerConfig(hold_frames=2)
    fr = FleetRunner(model, cfg, chunk_size=8, block_d=64)
    out = fr.process(frames)
    # the derived StreamStats are identical, stream by stream
    rep = fleet_report(out[1], out[2], labels)
    assert rep.n_sensors == 4 and rep.n_frames == 21
    for s in range(4):
        ref = simulate_stream_batched(model, frames[s], labels[s], cfg,
                                      chunk_size=8, block_d=64)
        got = rep.stats[s]
        np.testing.assert_array_equal(got.decisions, ref.decisions)
        np.testing.assert_array_equal(got.gated_on, ref.gated_on)
        assert got.duty_cycle == ref.duty_cycle
        assert got.missed_positive == ref.missed_positive
        assert got.false_active == ref.false_active


def test_fleet_state_carries_across_process_calls():
    model = make_model()
    frames, _ = make_fleet(S=3, N=23)
    cfg = ControllerConfig(hold_frames=3)
    whole = FleetRunner(model, cfg, chunk_size=8)
    s_all, f_all, g_all = whole.process(frames)
    split = FleetRunner(model, cfg, chunk_size=8)
    parts = [split.process(frames[:, a:z])
             for a, z in [(0, 7), (7, 10), (10, 23)]]
    np.testing.assert_allclose(
        np.concatenate([p[0] for p in parts], axis=1), s_all,
        rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        np.concatenate([p[1] for p in parts], axis=1), f_all)
    np.testing.assert_array_equal(
        np.concatenate([p[2] for p in parts], axis=1), g_all)


def test_fleet_rejects_bad_inputs():
    model = make_model()
    with pytest.raises(ValueError):
        FleetRunner(model, chunk_size=0)
    with pytest.raises(ValueError):            # noise without an ADC
        FleetRunner(model, adc_sigma=0.05)
    r = FleetRunner(model)
    with pytest.raises(ValueError):
        r.process(jnp.zeros((4, 24, 24)))          # missing sensor axis
    frames, _ = make_fleet(S=2, N=5)
    r.process(frames)
    with pytest.raises(ValueError):                # fleet size changed
        r.process(jnp.zeros((3, 5, 24, 24)))


# ---------------------------------------------------------------------------
# ADC in the loop
# ---------------------------------------------------------------------------

def test_fleet_adc_internal_equals_prequantized():
    model = make_model()
    frames, _ = make_fleet(S=3, N=13)
    cfg = ControllerConfig(hold_frames=2)
    internal = FleetRunner(model, cfg, chunk_size=4, adc_bits=4)
    s_i, f_i, g_i = internal.process(frames)
    pre = FleetRunner(model, cfg, chunk_size=4)
    s_p, f_p, g_p = pre.process(adc.quantize(frames, 4))
    np.testing.assert_array_equal(s_i, s_p)
    np.testing.assert_array_equal(f_i, f_p)
    np.testing.assert_array_equal(g_i, g_p)


def test_fleet_noisy_adc_matches_independent_runners():
    """Per-(stream, frame-index) noise keys: stream s's ADC captures are
    exactly ``adc_view`` keyed by ``fold_in(adc_key, s)`` — an
    independent noiseless runner fed those captures sees the same."""
    model = make_model()
    frames, _ = make_fleet(S=3, N=11)
    cfg = ControllerConfig(hold_frames=2)
    base = jax.random.PRNGKey(5)
    fr = FleetRunner(model, cfg, chunk_size=4, adc_bits=4, adc_sigma=0.02,
                     adc_key=base)
    out = fr.process(frames)
    singles = []
    for s in range(3):
        captured = adc_view(frames[s], 4, sigma=0.02,
                            key=jax.random.fold_in(base, s))
        r = StreamRunner(model, cfg, chunk_size=4, adc_bits=4)
        singles.append(r.process(captured))
    assert_streams_equal(out, singles)


# ---------------------------------------------------------------------------
# sensor-axis sharding (shard_map)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fleet_sharded_matches_unsharded(backend):
    """Under a mesh the sensor axis is shard_map'd; results are unchanged.

    On a 1-device host this exercises the shard_map code path with a
    trivial mesh; the CI job forces 8 host devices so the same assertion
    covers a real multi-device partitioning of the sensor axis.
    """
    model = make_model()
    S = 8
    frames, _ = make_fleet(S=S, N=7)
    cfg = ControllerConfig(hold_frames=2)
    plain = FleetRunner(model, cfg, chunk_size=4, backend=backend,
                        block_d=64)
    s0, f0, g0 = plain.process(frames)
    n_dev = jax.device_count()
    data = n_dev if S % n_dev == 0 else 1
    mesh = make_mesh((data, n_dev // data), ("data", "model"))
    with shlib.use_mesh(mesh):
        sharded = FleetRunner(model, cfg, chunk_size=4, backend=backend,
                              block_d=64)
        s1, f1, g1 = sharded.process(frames)
    np.testing.assert_allclose(s0, s1, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(f0, f1)
    np.testing.assert_array_equal(g0, g1)


def test_fleet_int8_per_stream_adapt_backend_parity():
    """The per-stream-adapt x int8 cell: retile_classes_int_fleet feeding
    the kernel's stream-indexed int8 class tiles must agree with the jnp
    oracle, and the per-stream classifiers must actually diverge."""
    from repro.core.online import AdaptConfig

    model = make_model()
    frames, labels = make_fleet(S=3, N=9)
    cfg = ControllerConfig(hold_frames=1)
    ad = AdaptConfig(mode="label", lr=1.0, scope="per-stream")
    outs = {}
    for backend in ("jnp", "pallas"):
        r = FleetRunner(model, cfg, chunk_size=4, backend=backend,
                        block_d=64, adc_bits=8, precision="int8", adapt=ad)
        outs[backend] = r.process(frames, labels=labels)
        assert r.class_hvs.shape[0] == 3
        # streams saw different samples -> different classifiers
        assert not np.allclose(np.asarray(r.class_hvs[0]),
                               np.asarray(r.class_hvs[1]))
    np.testing.assert_allclose(outs["pallas"][0], outs["jnp"][0],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(outs["pallas"][1], outs["jnp"][1])


def test_fleet_closed_loop_sharded_matches_unsharded():
    """The closed capture loop composes with sensor-axis sharding: the
    per-stream (hold, phase) ADC state rides the partitioned StreamState
    and the control scan emits no collectives — shard_map'd closed-loop
    super-chunks == the unsharded step, capture log included."""
    from repro.core.sensor_control import CaptureConfig

    model = make_model()
    S = 8
    frames, _ = make_fleet(S=S, N=7)
    cfg = ControllerConfig(base_rate_hz=15, active_rate_hz=60,
                           hold_frames=2)
    plain = FleetRunner(model, cfg, chunk_size=4, block_d=64,
                        control=CaptureConfig(hp_buffer=0))
    s0, f0, g0 = plain.process(frames)
    n_dev = jax.device_count()
    data = n_dev if S % n_dev == 0 else 1
    mesh = make_mesh((data, n_dev // data), ("data", "model"))
    with shlib.use_mesh(mesh):
        sharded = FleetRunner(model, cfg, chunk_size=4, block_d=64,
                              control=CaptureConfig(hp_buffer=0))
        s1, f1, g1 = sharded.process(frames)
    np.testing.assert_allclose(s0, s1, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(f0, f1)
    np.testing.assert_array_equal(g0, g1)
    np.testing.assert_array_equal(plain.capture_log.sampled,
                                  sharded.capture_log.sampled)
    assert plain.capture_log.sampled.sum() < S * 7   # loop actually closed


def test_fleet_int8_sharded_matches_unsharded():
    """The int8 ADC-code datapath composes with sensor-axis sharding:
    shard_map'd integer super-chunks == the unsharded step (the int tiles
    ride the replicated spec exactly like the float tiles)."""
    model = make_model()
    S = 8
    frames, _ = make_fleet(S=S, N=6)
    cfg = ControllerConfig(hold_frames=2)
    plain = FleetRunner(model, cfg, chunk_size=4, block_d=64, adc_bits=8,
                        precision="int8")
    s0, f0, g0 = plain.process(frames)
    n_dev = jax.device_count()
    data = n_dev if S % n_dev == 0 else 1
    mesh = make_mesh((data, n_dev // data), ("data", "model"))
    with shlib.use_mesh(mesh):
        sharded = FleetRunner(model, cfg, chunk_size=4, block_d=64,
                              adc_bits=8, precision="int8")
        s1, f1, g1 = sharded.process(frames)
    np.testing.assert_allclose(s0, s1, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(f0, f1)
    np.testing.assert_array_equal(g0, g1)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >1 device "
                           "(XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8)")
def test_fleet_sensor_axis_actually_partitioned():
    """With a real multi-device mesh the "sensors" rule claims the data
    axis — the step's sharded inputs split S across devices."""
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    with shlib.use_mesh(mesh):
        spec = shlib.spec_for((jax.device_count() * 2,), ("sensors",))
    assert spec[0] is not None


@pytest.mark.parametrize("S", [3, 5, 9])
def test_fleet_non_divisible_sensor_axis_pads_and_shards(S):
    """S that doesn't divide the mesh is padded with masked slots — the
    step still shard_maps (never an unsharded fallback, never an error)
    and every real stream's outputs are bitwise-identical. On the CI
    8-device mesh S=5 pads to 8 and S=9 pads to 16."""
    from repro.sensing import fleet as fleet_mod

    model = make_model()
    frames, _ = make_fleet(S=S, N=5)
    cfg = ControllerConfig(hold_frames=1)
    if jax.device_count() % S == 0:
        pytest.skip(f"device count divisible by {S}")
    plain = FleetRunner(model, cfg, chunk_size=4)
    s0, f0, g0 = plain.process(frames)
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    with shlib.use_mesh(mesh):
        r = FleetRunner(model, cfg, chunk_size=4)
        # the sensors axis must still be claimed (padding, not fallback)
        axes, k = fleet_mod._sensor_axes(mesh)
        assert axes == ("data",) and k == jax.device_count()
        s1, f1, g1 = r.process(frames)
        assert r.service._step_axes[0] == ("data",)   # the step is sharded
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_array_equal(f0, f1)
    np.testing.assert_array_equal(g0, g1)
    # carried state stays at the real S (pad slots never leak out)
    assert r.holds.shape == (S,)


def test_fleet_shared_adapt_sharded_no_fallback():
    """Shared-scope online adaptation now shards (all_gathered samples +
    replicated fold) instead of falling back to the unsharded step, and
    the adapted classifier matches unsharded bitwise."""
    from repro.core.online import AdaptConfig
    from repro.sensing import fleet as fleet_mod

    model = make_model()
    S = 8
    frames, labels = make_fleet(S=S, N=7)
    cfg = ControllerConfig(hold_frames=1)
    ad = AdaptConfig(mode="label", lr=0.5, scope="shared")
    plain = FleetRunner(model, cfg, chunk_size=4, adapt=ad)
    s0, f0, g0 = plain.process(frames, labels=labels)
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    with shlib.use_mesh(mesh):
        r = FleetRunner(model, cfg, chunk_size=4, adapt=ad)
        s1, f1, g1 = r.process(frames, labels=labels)
        assert r.service._step_axes[0] == ("data",)   # no fallback
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_array_equal(f0, f1)
    np.testing.assert_array_equal(g0, g1)
    np.testing.assert_array_equal(np.asarray(plain.class_hvs),
                                  np.asarray(r.class_hvs))
    # the shared classifier actually moved (the fold is not a no-op)
    assert not np.allclose(np.asarray(r.class_hvs),
                           np.asarray(model.class_hvs))


# ---------------------------------------------------------------------------
# fleet energy report
# ---------------------------------------------------------------------------

def test_simulate_fleet_report_accounting():
    model = make_model()
    frames, labels = make_fleet(S=4, N=16)
    rep = simulate_fleet(model, frames, labels,
                         ControllerConfig(hold_frames=2), chunk_size=8)
    assert rep.n_sensors == 4 and rep.n_frames == 16
    assert len(rep.stats) == 4
    duties = [s.duty_cycle for s in rep.stats]
    assert rep.duty_cycle == pytest.approx(float(np.mean(duties)))
    # totals: sum of per-stream measured breakdowns x frames
    p = energy.EnergyParams()
    want = sum(energy.hypersense_measured(d, p).total for d in duties) * 16
    assert rep.energy_total_j == pytest.approx(want)
    assert rep.baseline_total_j == pytest.approx(
        energy.conventional(p).total * 4 * 16)
    # an idle-dominated fleet saves energy vs always-on
    assert 0.0 < rep.total_saving < 1.0


def test_hypersense_measured_consistent_with_roc_form():
    p = energy.EnergyParams()
    d = energy.duty_cycle(0.1, 0.95, 0.01)
    a = energy.hypersense(0.1, 0.95, 0.01, p)
    b = energy.hypersense_measured(d, p)
    assert a == b


def test_int8_precision_bills_cheaper_hdc():
    """The int8 datapath reduces exactly the always-on HDC component."""
    p = energy.EnergyParams()
    f32 = energy.hypersense_measured(0.1, p)
    i8 = energy.hypersense_measured(0.1, p, precision="int8")
    assert i8.hdc == pytest.approx(f32.hdc * p.hdc_int8_factor)
    assert (i8.sensor, i8.adc, i8.comm, i8.cloud) == (
        f32.sensor, f32.adc, f32.comm, f32.cloud)
    assert i8.total < f32.total
    with pytest.raises(ValueError):
        energy.hypersense_measured(0.1, p, precision="fp16")
    # ...and the fleet report threads it through
    model = make_model()
    frames, labels = make_fleet(S=2, N=8)
    r = FleetRunner(model, ControllerConfig(hold_frames=1), chunk_size=4,
                    adc_bits=8, precision="int8")
    _, fired, gated = r.process(frames)
    rep_i8 = fleet_report(fired, gated, labels, precision="int8")
    rep_f32 = fleet_report(fired, gated, labels)
    assert rep_i8.energy_total_j < rep_f32.energy_total_j
