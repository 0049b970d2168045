"""Closed-loop capture runtime invariants (ISSUE 5).

The contracts that make gate-driven variable-rate/-precision capture
safe to turn on:

* ``control_scan`` (the jittable rate-aware controller) is exactly
  :class:`~repro.core.sensor_control.RateController` for arbitrary
  decision sequences, decimations, and carried-in state;
* with the loop *disabled* (``subsample=False``, or
  ``base_rate_hz == active_rate_hz``) the closed-loop runners are
  **bitwise identical** to the open-loop runners — on both backends and
  both precisions;
* capture-log billing (:func:`repro.core.energy.from_capture_log`)
  reduces *exactly* to the duty-fraction account
  (:func:`~repro.core.energy.hypersense_measured`) when every frame is
  sampled, and strictly undercuts it when idle frames are skipped;
* the HP burst deliverable is the ``hp_bits`` quantization of the raw
  frames at exactly the gated indices, bounded by the buffer size;
* stream slicing stays invisible with the control state in the carry,
  and a closed-loop fleet equals independent closed-loop stream runners.
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

from repro.core import encoding, energy, hypersense
from repro.core.sensor_control import (CaptureConfig, CaptureLog,
                                       ControllerConfig, RateController,
                                       decimation, stats_from)
from repro.launch.replay import FleetRunner, StreamRunner
from repro.sensing import adc, synthetic
from repro.sensing.fleet import fleet_report
from repro.sensing.stream import control_scan, hp_capture

jax.config.update("jax_platform_name", "cpu")


def key(i):
    return jax.random.PRNGKey(i)


def make_model(h=6, w=6, stride=3, D=128, t_score=0.0, t_detection=2):
    B0, b = encoding.make_perm_base_rows(key(1), h, D)
    C = jax.random.normal(key(2), (2, D))
    return hypersense.HyperSenseModel(C, B0, b, h, w, stride,
                                      t_score=t_score,
                                      t_detection=t_detection)


_FRAMES = {}


def stream_inputs(n=41, seed=3):
    if (n, seed) not in _FRAMES:
        cfg = synthetic.RadarConfig(height=24, width=24)
        frames, _, labels = synthetic.make_dataset(key(seed), n, cfg)
        _FRAMES[(n, seed)] = (frames, np.asarray(labels))
    return _FRAMES[(n, seed)]


# ---------------------------------------------------------------------------
# control_scan == RateController
# ---------------------------------------------------------------------------

def test_decimation_values_and_validation():
    assert decimation(ControllerConfig(base_rate_hz=10,
                                       active_rate_hz=60)) == 6
    assert decimation(ControllerConfig(base_rate_hz=60,
                                       active_rate_hz=60)) == 1
    with pytest.raises(ValueError, match="cannot be slower"):
        decimation(ControllerConfig(base_rate_hz=60, active_rate_hz=10))
    with pytest.raises(ValueError, match="positive"):
        decimation(ControllerConfig(base_rate_hz=0.0))


@hypothesis.given(st.integers(0, 2**16), st.integers(0, 6),
                  st.integers(1, 8), st.integers(0, 6), st.integers(0, 7),
                  st.integers(1, 300))
@hypothesis.settings(max_examples=30, deadline=None)
def test_control_scan_matches_rate_controller(seed, hold, decim,
                                              init_hold, init_phase, n):
    """control_scan == RateController for arbitrary decision sequences,
    decimations, hold lengths, and carried-in (hold, phase) state."""
    rng = np.random.RandomState(seed)
    fired = rng.rand(n) < rng.uniform(0.0, 1.0)
    init_phase = min(init_phase, decim - 1)
    ctrl = RateController(ControllerConfig(
        base_rate_hz=60.0 / decim, active_rate_hz=60.0, hold_frames=hold))
    assert ctrl.decim == decim
    ctrl._hold, ctrl._phase = init_hold, init_phase
    want = [ctrl.step(bool(f)) for f in fired]
    smp, gt, holds, phases = control_scan(jnp.asarray(fired), hold, decim,
                                          init_hold, init_phase)
    np.testing.assert_array_equal(np.asarray(smp),
                                  np.array([w[0] for w in want]))
    np.testing.assert_array_equal(np.asarray(gt),
                                  np.array([w[1] for w in want]))
    assert int(holds[-1]) == ctrl._hold
    assert int(phases[-1]) == ctrl._phase
    # resuming from the carried state continues identically
    cut = rng.randint(1, n) if n > 1 else 1
    s_a, g_a, h_a, p_a = control_scan(jnp.asarray(fired[:cut]), hold,
                                      decim, init_hold, init_phase)
    s_b, g_b, _, _ = control_scan(jnp.asarray(fired[cut:]), hold, decim,
                                  h_a[-1], p_a[-1])
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(g_a), np.asarray(g_b)]),
        np.asarray(gt))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s_a), np.asarray(s_b)]),
        np.asarray(smp))


def test_control_scan_decim_one_is_gate_scan():
    """decim == 1: every frame sampled, gated == gate_scan bitwise."""
    from repro.sensing.stream import gate_scan
    rng = np.random.RandomState(7)
    fired = jnp.asarray(rng.rand(200) < 0.2)
    smp, gt, holds, _ = control_scan(fired, 3, 1)
    want_g, want_h = gate_scan(fired, 3)
    assert bool(np.asarray(smp).all())
    np.testing.assert_array_equal(np.asarray(gt), np.asarray(want_g))
    np.testing.assert_array_equal(np.asarray(holds), np.asarray(want_h))


def test_idle_decimation_schedule():
    """No detections: exactly one LP sample per decim period, starting at
    frame 0 — the base_rate_hz trickle."""
    smp, gt, _, _ = control_scan(jnp.zeros(20, bool), 3, 4)
    np.testing.assert_array_equal(np.asarray(smp),
                                  np.arange(20) % 4 == 0)
    assert not np.asarray(gt).any()


# ---------------------------------------------------------------------------
# closed loop disabled == open loop, bitwise (both backends/precisions)
# ---------------------------------------------------------------------------

RATES = ControllerConfig(base_rate_hz=10, active_rate_hz=60,
                         hold_frames=3)


@pytest.mark.parametrize("backend,precision", [
    ("jnp", "float32"), ("pallas", "float32"),
    ("jnp", "int8"), ("pallas", "int8"),
])
def test_disabled_control_bitwise_identical(backend, precision):
    """subsample=False AND base==active: both bitwise == control=None."""
    frames, _ = stream_inputs()
    model = make_model()
    kw = dict(chunk_size=8, backend=backend, precision=precision,
              block_d=64)
    if precision == "int8":
        kw["adc_bits"] = 8
    ref = StreamRunner(model, RATES, **kw)
    s0, f0, g0 = ref.process(frames)
    off = StreamRunner(model, RATES, **kw,
                       control=CaptureConfig(subsample=False, hp_buffer=0))
    s1, f1, g1 = off.process(frames)
    flat = ControllerConfig(base_rate_hz=60, active_rate_hz=60,
                            hold_frames=3)
    same = StreamRunner(model, flat, **kw,
                        control=CaptureConfig(hp_buffer=0))
    s2, f2, g2 = same.process(frames)
    for s, f, g in [(s1, f1, g1), (s2, f2, g2)]:
        np.testing.assert_array_equal(s, s0)
        np.testing.assert_array_equal(f, f0)
        np.testing.assert_array_equal(g, g0)
    assert off.capture_log.sampled.all()
    assert same.service._decim == 1 and off.service._decim == 1


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fleet_disabled_control_bitwise_identical(backend):
    frames, _ = stream_inputs(22)
    fl = jnp.stack([frames[:11], frames[11:]])
    model = make_model()
    ref = FleetRunner(model, RATES, chunk_size=4, backend=backend,
                      block_d=64)
    s0, f0, g0 = ref.process(fl)
    off = FleetRunner(model, RATES, chunk_size=4, backend=backend,
                      block_d=64,
                      control=CaptureConfig(subsample=False, hp_buffer=0))
    s1, f1, g1 = off.process(fl)
    np.testing.assert_array_equal(s1, s0)
    np.testing.assert_array_equal(f1, f0)
    np.testing.assert_array_equal(g1, g0)
    assert off.capture_log.sampled.all()


# ---------------------------------------------------------------------------
# closed-loop semantics
# ---------------------------------------------------------------------------

def test_unsampled_frames_never_fire():
    """A frame the LP ADC skipped can never fire or open the gate."""
    frames, _ = stream_inputs()
    model = make_model()
    r = StreamRunner(model, RATES, chunk_size=8,
                     control=CaptureConfig(hp_buffer=0))
    _, fired, gated = r.process(frames)
    log = r.capture_log
    assert not (fired & ~log.sampled).any()
    # idle stretches are decimated: strictly fewer conversions than frames
    assert log.sampled.sum() < len(frames)
    # and every gated-on frame traces back to a sampled firing frame
    assert log.gated.shape == (len(frames),)


def test_closed_loop_slicing_invariance():
    """Arbitrary process() slicing is invisible to the closed loop — the
    (hold, phase) ADC state and the capture log carry across calls."""
    frames, _ = stream_inputs()
    model = make_model()
    whole = StreamRunner(model, RATES, chunk_size=8,
                         control=CaptureConfig())
    s_all, f_all, g_all = whole.process(frames)
    log_all = whole.capture_log
    idx_all, hp_all = whole.drain_hp()
    split = StreamRunner(model, RATES, chunk_size=8,
                         control=CaptureConfig())
    parts = [split.process(frames[a:z])
             for a, z in [(0, 7), (7, 10), (10, 41)]]
    np.testing.assert_array_equal(np.concatenate([p[0] for p in parts]),
                                  s_all)
    np.testing.assert_array_equal(np.concatenate([p[1] for p in parts]),
                                  f_all)
    np.testing.assert_array_equal(np.concatenate([p[2] for p in parts]),
                                  g_all)
    np.testing.assert_array_equal(split.capture_log.sampled,
                                  log_all.sampled)
    np.testing.assert_array_equal(split.capture_log.gated, log_all.gated)
    idx_s, hp_s = split.drain_hp()
    np.testing.assert_array_equal(idx_s, idx_all)
    if len(idx_all):
        np.testing.assert_array_equal(hp_s, hp_all)


def test_fleet_control_equals_independent_runners():
    """Closed-loop fleet == S independent closed-loop stream runners."""
    frames, _ = stream_inputs(22)
    fl = jnp.stack([frames[:11], frames[11:]])
    model = make_model()
    fleet = FleetRunner(model, RATES, chunk_size=4,
                        control=CaptureConfig())
    s, f, g = fleet.process(fl)
    flog = fleet.capture_log
    fhp = fleet.drain_hp()
    for si in range(2):
        r = StreamRunner(model, RATES, chunk_size=4,
                         control=CaptureConfig())
        s1, f1, g1 = r.process(fl[si])
        np.testing.assert_array_equal(s[si], s1)
        np.testing.assert_array_equal(f[si], f1)
        np.testing.assert_array_equal(g[si], g1)
        np.testing.assert_array_equal(flog.sampled[si],
                                      r.capture_log.sampled)
        idx1, hp1 = r.drain_hp()
        np.testing.assert_array_equal(fhp[si][0], idx1)
        if len(idx1):
            np.testing.assert_array_equal(fhp[si][1], hp1)


# ---------------------------------------------------------------------------
# HP burst deliverable (bounded gather buffer)
# ---------------------------------------------------------------------------

def test_hp_frames_are_hp_quantized_gated_frames():
    frames, _ = stream_inputs()
    model = make_model(t_score=-10.0, t_detection=0)  # fires on everything
    r = StreamRunner(model, RATES, chunk_size=8,
                     control=CaptureConfig(hp_bits=12))
    _, _, gated = r.process(frames)
    assert gated.all()
    idx, hp = r.drain_hp()
    np.testing.assert_array_equal(idx, np.arange(len(frames)))
    np.testing.assert_array_equal(
        hp, np.asarray(adc.quantize(frames, 12)))
    assert r.hp_dropped == 0
    # drained: a second drain is empty, new frames refill from abs index
    idx2, _ = r.drain_hp()
    assert len(idx2) == 0
    r.process(frames[:5])
    idx3, _ = r.drain_hp()
    np.testing.assert_array_equal(idx3, len(frames) + np.arange(5))


def test_hp_buffer_bound_drops_and_counts():
    """A chunk with more bursts than buffer slots keeps the FIRST k gated
    frames (in order) and counts the spill in hp_dropped."""
    frames, _ = stream_inputs()
    model = make_model(t_score=-10.0, t_detection=0)
    r = StreamRunner(model, RATES, chunk_size=8,
                     control=CaptureConfig(hp_bits=12, hp_buffer=2))
    r.process(frames[:16])
    idx, hp = r.drain_hp()
    np.testing.assert_array_equal(idx, [0, 1, 8, 9])  # first 2 per chunk
    assert r.hp_dropped == 16 - 4
    np.testing.assert_array_equal(
        hp, np.asarray(adc.quantize(frames, 12))[[0, 1, 8, 9]])


def test_hp_capture_helper_masks_padding():
    raw = jnp.asarray(np.random.RandomState(0).rand(6, 4, 4),
                      jnp.float32)
    gated = jnp.asarray([True, False, True, True, True, True])
    buf, idx, cnt = hp_capture(raw, gated, jnp.int32(4), 3, 10)
    # frames 4, 5 are padding (n_valid=4): only 0, 2, 3 qualify
    np.testing.assert_array_equal(np.asarray(idx), [0, 2, 3])
    assert int(cnt) == 3
    np.testing.assert_array_equal(
        np.asarray(buf), np.asarray(adc.quantize(raw, 10))[[0, 2, 3]])


def test_precoded_int8_input_requires_log_only():
    frames, _ = stream_inputs()
    codes = adc.pack_codes(adc.quantize_codes(frames, 8), 8)
    r = StreamRunner(make_model(), RATES, chunk_size=8, adc_bits=8,
                     precision="int8", control=CaptureConfig())
    with pytest.raises(ValueError, match="raw frames"):
        r.process(codes)
    ok = StreamRunner(make_model(), RATES, chunk_size=8, adc_bits=8,
                      precision="int8",
                      control=CaptureConfig(hp_buffer=0))
    ok.process(codes)
    assert ok.capture_log.sampled.sum() < len(frames)


# ---------------------------------------------------------------------------
# capture-log energy billing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision,adc_bits", [("float32", None),
                                                ("float32", 4),
                                                ("int8", 8)])
def test_capture_billing_equals_duty_billing_when_all_sampled(precision,
                                                              adc_bits):
    """Open loop (every frame LP-converted): from_capture_log reduces
    EXACTLY to hypersense_measured(duty) — same fields, bitwise."""
    frames, labels = stream_inputs()
    model = make_model()
    r = StreamRunner(model, RATES, chunk_size=8, adc_bits=adc_bits,
                     precision=precision)
    _, fired, gated = r.process(frames)
    log = r.capture_log
    assert log.sampled.all()
    stats = stats_from(fired, gated, labels)
    # exact reduction needs the params' LP depth to be the converter's
    # (with adc_bits=None the log falls back to the params' default)
    lp = adc_bits if adc_bits is not None else 4
    for params in [energy.EnergyParams(adc_lp_bits=lp),
                   energy.EnergyParams(adc_lp_bits=lp, adc_hp_j=0.4,
                                       cloud_j=2.0)]:
        got = energy.from_capture_log(log, params, precision)
        want = energy.hypersense_measured(stats.duty_cycle, params,
                                          precision)
        assert got == want


def test_capture_billing_undercuts_duty_billing_when_subsampled():
    """Idle decimation shows up as real Joules the duty-fraction account
    cannot see: lower adc + hdc terms, same comm/cloud at equal duty."""
    frames, labels = stream_inputs()
    model = make_model()
    r = StreamRunner(model, RATES, chunk_size=8,
                     control=CaptureConfig(hp_buffer=0))
    _, fired, gated = r.process(frames)
    log = r.capture_log
    assert 0 < log.sampled.sum() < len(frames)
    stats = stats_from(fired, gated, labels)
    got = energy.from_capture_log(log)
    approx = energy.hypersense_measured(stats.duty_cycle)
    assert got.adc < approx.adc
    assert got.hdc < approx.hdc
    assert got.comm == approx.comm and got.cloud == approx.cloud
    assert got.total < approx.total


def test_from_capture_log_bits_and_counts():
    """Per-frame bits billed via the SAR 2^bits model; samples_converted
    counts LP + HP conversions."""
    log = CaptureLog(sampled=np.array([True, False, True, True]),
                     gated=np.array([False, False, True, True]),
                     lp_bits=4, hp_bits=12, frame_pixels=100)
    p = energy.EnergyParams()
    got = energy.from_capture_log(log, p)
    assert got.adc == pytest.approx(0.75 * p.adc_lp_j + 0.5 * p.adc_hp_j)
    assert got.hdc == pytest.approx(0.75 * p.hdc_accel_j)
    assert got.comm == pytest.approx(0.5 * p.comm_j)
    assert log.samples_converted() == (3 + 2) * 100
    assert energy.adc_conversion_j(p.adc_lp_bits, p) == p.adc_lp_j


def test_fleet_report_prefers_capture_log():
    frames, labels = stream_inputs(22)
    fl = jnp.stack([frames[:11], frames[11:]])
    fla = np.stack([labels[:11], labels[11:]])
    runner = FleetRunner(make_model(), RATES, chunk_size=4,
                         control=CaptureConfig(hp_buffer=0))
    _, fired, gated = runner.process(fl)
    rep_log = fleet_report(fired, gated, fla,
                           capture=runner.capture_log)
    rep_duty = fleet_report(fired, gated, fla)
    assert rep_log.energy_total_j < rep_duty.energy_total_j
    assert rep_log.baseline_total_j == rep_duty.baseline_total_j


# ---------------------------------------------------------------------------
# stats NaN propagation (ISSUE 5 satellite)
# ---------------------------------------------------------------------------

def test_fleet_report_propagates_nan_stats():
    """A stream with no object frames reports NaN missed_positive (not a
    perfect 0.0) through stats_from_batch/fleet_report; energy billing
    (duty-based) is unaffected."""
    fired = np.zeros((2, 6), bool)
    gated = np.zeros((2, 6), bool)
    gated[1, ::2] = True
    labels = np.stack([np.zeros(6, np.int32), np.ones(6, np.int32)])
    rep = fleet_report(fired, gated, labels)
    assert np.isnan(rep.stats[0].missed_positive)
    assert rep.stats[0].false_active == 0.0
    assert np.isnan(rep.stats[1].false_active)
    assert rep.stats[1].missed_positive == 0.5
    assert np.isfinite(rep.energy_total_j)
