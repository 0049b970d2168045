"""Streaming runtime: chunked batched path == frame-at-a-time reference.

A deterministic synthetic stream through chunked scoring +
``SensorController`` gating must produce *identical* ``StreamStats`` to the
existing per-frame ``simulate_stream``; the ``gate_scan`` hysteresis must
match the stateful controller bit-for-bit; and chunk size must be
invisible (including non-divisible tails and state across ``process``
calls).
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

from repro.core import encoding, hypersense
from repro.core.sensor_control import (ControllerConfig, SensorController,
                                       simulate_stream)
from repro.launch.replay import StreamRunner, simulate_stream_batched
from repro.sensing import adc, synthetic
from repro.sensing.stream import gate_scan

jax.config.update("jax_platform_name", "cpu")


def key(i):
    return jax.random.PRNGKey(i)


def make_model(h=6, w=6, stride=3, D=128, t_score=0.0, t_detection=2):
    B0, b = encoding.make_perm_base_rows(key(1), h, D)
    C = jax.random.normal(key(2), (2, D))
    return hypersense.HyperSenseModel(C, B0, b, h, w, stride,
                                      t_score=t_score,
                                      t_detection=t_detection)


# ---------------------------------------------------------------------------
# gate_scan == SensorController
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hold", [0, 1, 3, 7])
def test_gate_scan_matches_controller(hold):
    rng = np.random.RandomState(hold)
    fired = rng.rand(300) < 0.15
    ctrl = SensorController(ControllerConfig(hold_frames=hold))
    want = np.array([ctrl.step(bool(f)) for f in fired])
    got, holds = gate_scan(jnp.asarray(fired), hold)
    np.testing.assert_array_equal(np.asarray(got), want)
    # resuming from an intermediate hold state must continue identically
    cut = 117
    got_a, holds_a = gate_scan(jnp.asarray(fired[:cut]), hold)
    got_b, _ = gate_scan(jnp.asarray(fired[cut:]), hold, holds_a[-1])
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(got_a), np.asarray(got_b)]), want)


@hypothesis.given(st.integers(0, 2**16), st.integers(0, 6),
                  st.integers(0, 6), st.integers(1, 400))
@hypothesis.settings(max_examples=30, deadline=None)
def test_gate_scan_matches_controller_property(seed, hold, init_hold, n):
    """gate_scan == SensorController for *arbitrary* decision sequences —
    any length, any hold_frames (incl. 0), any carried-in init_hold."""
    rng = np.random.RandomState(seed)
    fired = rng.rand(n) < rng.uniform(0.0, 1.0)
    ctrl = SensorController(ControllerConfig(hold_frames=hold))
    ctrl._hold = init_hold
    want_g, want_h = [], []
    for f in fired:
        want_g.append(ctrl.step(bool(f)))
        want_h.append(ctrl._hold)
    got_g, got_h = gate_scan(jnp.asarray(fired), hold, init_hold)
    np.testing.assert_array_equal(np.asarray(got_g), np.array(want_g))
    np.testing.assert_array_equal(np.asarray(got_h), np.array(want_h))


@hypothesis.given(st.integers(0, 2**16), st.integers(0, 5),
                  st.integers(2, 50))
@hypothesis.settings(max_examples=30, deadline=None)
def test_gate_scan_split_resume_property(seed, hold, n):
    """Splitting a decision sequence at any point and resuming from the
    carried hold state is invisible — for every cut position."""
    rng = np.random.RandomState(seed)
    fired = rng.rand(n) < 0.3
    want, _ = gate_scan(jnp.asarray(fired), hold)
    cut = rng.randint(1, n)
    got_a, holds_a = gate_scan(jnp.asarray(fired[:cut]), hold)
    got_b, _ = gate_scan(jnp.asarray(fired[cut:]), hold, holds_a[-1])
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(got_a), np.asarray(got_b)]),
        np.asarray(want))


# ---------------------------------------------------------------------------
# chunked streaming == frame-at-a-time simulate_stream
# ---------------------------------------------------------------------------

def _reference_stats(model, frames, labels, config):
    decide = jax.jit(lambda f: hypersense.detect(model, f))
    return simulate_stream(lambda f: bool(decide(f)), np.asarray(frames),
                           np.asarray(labels), config)


@pytest.mark.parametrize("chunk_size", [1, 5, 16, 64])
def test_batched_stream_matches_reference(chunk_size):
    model = make_model()
    cfg = synthetic.RadarConfig(height=24, width=24)
    frames, _, labels = synthetic.make_dataset(key(3), 41, cfg)
    config = ControllerConfig(hold_frames=2)
    ref = _reference_stats(model, frames, labels, config)
    got = simulate_stream_batched(model, frames, labels, config,
                                  chunk_size=chunk_size, backend="jnp")
    np.testing.assert_array_equal(got.decisions, ref.decisions)
    np.testing.assert_array_equal(got.gated_on, ref.gated_on)
    assert got.duty_cycle == ref.duty_cycle
    assert got.missed_positive == ref.missed_positive
    assert got.false_active == ref.false_active


def test_batched_stream_pallas_backend_matches_reference():
    model = make_model()
    cfg = synthetic.RadarConfig(height=24, width=24)
    frames, _, labels = synthetic.make_dataset(key(4), 19, cfg)
    config = ControllerConfig(hold_frames=1)
    ref = _reference_stats(model, frames, labels, config)
    got = simulate_stream_batched(model, frames, labels, config,
                                  chunk_size=8, backend="pallas",
                                  block_d=64)
    np.testing.assert_array_equal(got.decisions, ref.decisions)
    np.testing.assert_array_equal(got.gated_on, ref.gated_on)


def test_t_detection_beyond_fragment_count_never_fires():
    """detect() can never fire when t_detection >= my*mx; stream agrees."""
    model = make_model(t_detection=10_000)
    cfg = synthetic.RadarConfig(height=24, width=24)
    frames, _, labels = synthetic.make_dataset(key(5), 9, cfg)
    got = simulate_stream_batched(model, frames, labels,
                                  ControllerConfig(hold_frames=2),
                                  chunk_size=4, backend="jnp")
    assert not got.decisions.any()
    assert not got.gated_on.any()
    ref = _reference_stats(model, frames, labels,
                           ControllerConfig(hold_frames=2))
    np.testing.assert_array_equal(got.decisions, ref.decisions)


def test_runner_state_carries_across_process_calls():
    """Feeding the stream in arbitrary slices == feeding it at once."""
    model = make_model()
    cfg = synthetic.RadarConfig(height=24, width=24)
    frames, _, _ = synthetic.make_dataset(key(6), 23, cfg)
    whole = StreamRunner(model, ControllerConfig(hold_frames=3),
                         chunk_size=8)
    s_all, f_all, g_all = whole.process(frames)
    split = StreamRunner(model, ControllerConfig(hold_frames=3),
                         chunk_size=8)
    parts = [split.process(frames[a:z])
             for a, z in [(0, 7), (7, 10), (10, 23)]]
    np.testing.assert_allclose(np.concatenate([p[0] for p in parts]), s_all,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.concatenate([p[1] for p in parts]),
                                  f_all)
    np.testing.assert_array_equal(np.concatenate([p[2] for p in parts]),
                                  g_all)


_PROP = {}


def _prop_fixture():
    """Module-cached model + stream + whole-stream reference outputs."""
    if not _PROP:
        model = make_model()
        cfg = synthetic.RadarConfig(height=24, width=24)
        frames, _, _ = synthetic.make_dataset(key(7), 31, cfg)
        ref = {}
        for chunk_size in (1, 3, 8, 32):
            r = StreamRunner(model, ControllerConfig(hold_frames=3),
                             chunk_size=chunk_size)
            ref[chunk_size] = r.process(frames)
        # chunk size itself must be invisible
        for chunk_size in (3, 8, 32):
            np.testing.assert_allclose(ref[chunk_size][0], ref[1][0],
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_array_equal(ref[chunk_size][1], ref[1][1])
            np.testing.assert_array_equal(ref[chunk_size][2], ref[1][2])
        _PROP.update(model=model, frames=frames, ref=ref)
    return _PROP


@hypothesis.given(st.integers(0, 2**16), st.sampled_from([1, 3, 8, 32]))
@hypothesis.settings(max_examples=12, deadline=None)
def test_runner_slicing_invariance_property(seed, chunk_size):
    """process() output is invariant to HOW the stream is sliced into
    successive calls — random split points, random chunk_size (the
    generalization of test_runner_state_carries_across_process_calls)."""
    p = _prop_fixture()
    frames, (s_all, f_all, g_all) = p["frames"], p["ref"][chunk_size]
    n = frames.shape[0]
    rng = np.random.RandomState(seed)
    n_cuts = rng.randint(0, 6)
    cuts = sorted(rng.choice(np.arange(1, n), size=n_cuts, replace=False))
    bounds = [0, *cuts, n]
    runner = StreamRunner(p["model"], ControllerConfig(hold_frames=3),
                          chunk_size=chunk_size)
    parts = [runner.process(frames[a:z])
             for a, z in zip(bounds[:-1], bounds[1:])]
    np.testing.assert_allclose(np.concatenate([q[0] for q in parts]),
                               s_all, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.concatenate([q[1] for q in parts]),
                                  f_all)
    np.testing.assert_array_equal(np.concatenate([q[2] for q in parts]),
                                  g_all)


def test_runner_pallas_tail_chunk_padding():
    """n % chunk_size != 0 on the *pallas* backend: the padded tail chunk
    goes through the kernel and is masked identically to the jnp path."""
    model = make_model()
    cfg = synthetic.RadarConfig(height=24, width=24)
    frames, _, labels = synthetic.make_dataset(key(8), 11, cfg)
    config = ControllerConfig(hold_frames=2)
    ref = _reference_stats(model, frames, labels, config)
    got = simulate_stream_batched(model, frames, labels, config,
                                  chunk_size=8, backend="pallas",
                                  block_d=64)
    np.testing.assert_array_equal(got.decisions, ref.decisions)
    np.testing.assert_array_equal(got.gated_on, ref.gated_on)
    assert got.duty_cycle == ref.duty_cycle


def test_runner_adc_internal_equals_prequantized():
    """StreamRunner(adc_bits=b).process(raw) == plain runner fed
    adc.quantize(raw, b): quantization inside the runner is exactly the
    public quantize, and quantize is idempotent."""
    model = make_model()
    cfg = synthetic.RadarConfig(height=24, width=24)
    frames, _, _ = synthetic.make_dataset(key(9), 13, cfg)
    internal = StreamRunner(model, ControllerConfig(hold_frames=2),
                            chunk_size=4, adc_bits=4)
    s_i, f_i, g_i = internal.process(frames)
    pre = StreamRunner(model, ControllerConfig(hold_frames=2), chunk_size=4)
    s_p, f_p, g_p = pre.process(adc.quantize(frames, 4))
    np.testing.assert_array_equal(s_i, s_p)
    np.testing.assert_array_equal(f_i, f_p)
    np.testing.assert_array_equal(g_i, g_p)
    # ...and feeding an already-quantized stream through the ADC runner
    # changes nothing (idempotence end-to-end)
    again = StreamRunner(model, ControllerConfig(hold_frames=2),
                         chunk_size=4, adc_bits=4)
    s_q, f_q, g_q = again.process(adc.quantize(frames, 4))
    np.testing.assert_array_equal(s_q, s_i)
    np.testing.assert_array_equal(f_q, f_i)
    np.testing.assert_array_equal(g_q, g_i)


def test_runner_rejects_bad_chunk_size():
    with pytest.raises(ValueError):
        StreamRunner(make_model(), chunk_size=0)


def test_runner_rejects_sigma_without_bits():
    """adc_sigma without adc_bits would be silently ignored — reject it."""
    with pytest.raises(ValueError):
        StreamRunner(make_model(), adc_sigma=0.05)


# ---------------------------------------------------------------------------
# int8 ADC-code datapath through the runner
# ---------------------------------------------------------------------------

def test_runner_int8_requires_adc_bits_and_valid_precision():
    with pytest.raises(ValueError):
        StreamRunner(make_model(), precision="int8")    # no converter depth
    with pytest.raises(ValueError):
        StreamRunner(make_model(), precision="fp16", adc_bits=8)


def test_adc_view_codes_rejects_out_of_range_codes():
    """Codes from a deeper converter must be rejected, not silently
    wrapped modulo 256 by the uint8 pack."""
    from repro.sensing.stream import adc_view_codes

    frames = jnp.asarray(np.random.RandomState(0).rand(3, 24, 24) * 1.5,
                         jnp.float32)
    codes12 = adc.quantize_codes(frames, 12)        # values up to 4095
    with pytest.raises(ValueError, match="outside"):
        adc_view_codes(codes12, 8)
    # matching depth passes through exactly
    np.testing.assert_array_equal(
        np.asarray(adc_view_codes(codes12, 12)), np.asarray(codes12))
    r = StreamRunner(make_model(), chunk_size=4, adc_bits=8,
                     precision="int8")
    with pytest.raises(ValueError, match="outside"):
        r.process(codes12)


def test_runner_int8_internal_equals_precoded():
    """Feeding raw frames through the internal ADC == feeding the packed
    codes directly: the code stream is the runner's native input."""
    from repro.sensing.stream import adc_view_codes

    model = make_model()
    cfg = synthetic.RadarConfig(height=24, width=24)
    frames, _, _ = synthetic.make_dataset(key(14), 13, cfg)
    internal = StreamRunner(model, ControllerConfig(hold_frames=2),
                            chunk_size=4, adc_bits=8, precision="int8")
    s_i, f_i, g_i = internal.process(frames)
    codes = adc_view_codes(frames, 8)
    assert codes.dtype == jnp.uint8
    pre = StreamRunner(model, ControllerConfig(hold_frames=2),
                       chunk_size=4, adc_bits=8, precision="int8")
    s_p, f_p, g_p = pre.process(codes)
    np.testing.assert_array_equal(s_i, s_p)
    np.testing.assert_array_equal(f_i, f_p)
    np.testing.assert_array_equal(g_i, g_p)


def test_runner_int8_slicing_invariance():
    """The int8 path preserves the runners' core contract: output is
    invariant to how the stream is sliced into process() calls."""
    model = make_model()
    cfg = synthetic.RadarConfig(height=24, width=24)
    frames, _, _ = synthetic.make_dataset(key(15), 23, cfg)
    whole = StreamRunner(model, ControllerConfig(hold_frames=3),
                         chunk_size=8, adc_bits=8, precision="int8")
    s_all, f_all, g_all = whole.process(frames)
    split = StreamRunner(model, ControllerConfig(hold_frames=3),
                         chunk_size=8, adc_bits=8, precision="int8")
    parts = [split.process(frames[a:z])
             for a, z in [(0, 7), (7, 10), (10, 23)]]
    np.testing.assert_array_equal(np.concatenate([p[0] for p in parts]),
                                  s_all)
    np.testing.assert_array_equal(np.concatenate([p[1] for p in parts]),
                                  f_all)
    np.testing.assert_array_equal(np.concatenate([p[2] for p in parts]),
                                  g_all)
