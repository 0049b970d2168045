"""HP-drain shape contract + gated cascade backbone serving.

The contracts pinned here (repro/sensing/stream.py drain machinery,
repro/launch/steps.py detector cell, repro/launch/cascade.py service):

* ``drain_hp()`` ALWAYS returns frames shaped ``(M, H, W)`` — an empty
  drain after any processed frame is ``(0, H, W)``, never ``(0, 0, 0)``,
  on the service and both replay drivers (StreamRunner, FleetRunner), so
  consumers can concatenate drains blindly;
* drained indices are ABSOLUTE frame numbers, strictly increasing
  across drains, and chunked drain+concat == one-shot drain bitwise;
* drain → checkpoint → restore preserves exactly the undrained frames;
* the detector step is bitwise batch-invariant (``lax.map`` rows), so
  CascadeService's padded async batches == eager per-frame evaluation
  with exactly one backbone compile across ragged drain sizes;
* ``energy.from_capture_log`` handles a depth-less (open-loop) log
  explicitly: ``on_missing_bits="params"`` bills the params' depths,
  ``"error"`` refuses — and the cascade accounting uses ``"error"``.
"""

import os

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import encoding, energy, hypersense
from repro.core.sensor_control import (CaptureConfig, CaptureLog,
                                       ControllerConfig,
                                       assemble_capture_log)
from repro.launch import steps
from repro.launch.cascade import CascadeService
from repro.launch.replay import FleetRunner, StreamRunner
from repro.launch.serve import FleetService
from repro.sensing import adc
from repro.sensing.stream import hp_drain_arrays

C = 4          # chunk size
HW = (16, 16)  # frame shape (divisible by the detector patch)
CFG = ControllerConfig(hold_frames=2, base_rate_hz=10.0,
                       active_rate_hz=30.0)
CTL = CaptureConfig(hp_bits=12)


def make_model(t_score):
    B0, b = encoding.make_perm_base_rows(jax.random.PRNGKey(1), 6, 64)
    C_hvs = jax.random.normal(jax.random.PRNGKey(2), (2, 64))
    return hypersense.HyperSenseModel(C_hvs, B0, b, 6, 6, 3,
                                      t_score=t_score, t_detection=1)


NEVER = 1e9    # t_score no frame reaches -> gate never fires
ALWAYS = -1e9  # every scored frame fires -> HP bursts everywhere


def frames_of(n, seed=0, s=None):
    rng = np.random.default_rng(seed)
    shape = (n, *HW) if s is None else (s, n, *HW)
    return rng.normal(size=shape).astype(np.float32)


def drain_of(kind, model, trace):
    """Build runner `kind`, process `trace` (N,H,W), return drain_hp()."""
    if kind == "stream":
        r = StreamRunner(model, CFG, chunk_size=C, block_d=64,
                         control=CTL)
        r.process(trace)
        return r.drain_hp()
    if kind == "fleet":
        r = FleetRunner(model, CFG, chunk_size=C, block_d=64, control=CTL)
        r.process(trace[None])
        return r.drain_hp()[0]
    svc = FleetService(model, CFG, n_slots=1, chunk_size=C, block_d=64,
                       control=CTL)
    svc.attach(0)
    for t in range(0, len(trace), C):
        svc.dispatch({0: trace[t:t + C]})
    svc.flush()
    return svc.drain_hp(0)


# ---------------------------------------------------------------------------
# the (0, H, W) empty-drain shape contract  [regression: was (0, 0, 0)]
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["stream", "fleet", "service"])
def test_empty_drain_keeps_frame_shape(kind):
    """A drain with nothing captured still carries the real frame shape
    — the old (0, 0, 0) placeholder broke np.concatenate for every
    downstream consumer."""
    idx, frames = drain_of(kind, make_model(NEVER), frames_of(2 * C))
    assert idx.shape == (0,)
    assert frames.shape == (0, *HW)
    assert frames.dtype == np.float32
    # and it concatenates against a real burst, which is the point
    burst = np.ones((3, *HW), np.float32)
    assert np.concatenate([frames, burst]).shape == (3, *HW)


def test_empty_drain_before_any_frame_has_unknown_shape():
    r = StreamRunner(make_model(NEVER), CFG, chunk_size=C, block_d=64,
                     control=CTL)
    idx, frames = r.drain_hp()    # no frame ever seen: H, W unknowable
    assert idx.shape == (0,) and frames.shape == (0, 0, 0)


def test_hp_drain_arrays_shapes():
    idx, frames = hp_drain_arrays([], (7, 9))
    assert frames.shape == (0, 7, 9) and idx.dtype == np.int64
    idx, frames = hp_drain_arrays([], None)
    assert frames.shape == (0, 0, 0)
    idx, frames = hp_drain_arrays([(5, np.ones((7, 9)))], (7, 9))
    assert idx.tolist() == [5] and frames.shape == (1, 7, 9)
    assert frames.dtype == np.float32


# ---------------------------------------------------------------------------
# drains concatenate: interleaved empty/non-empty == one-shot, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["stream", "fleet", "service"])
def test_interleaved_drains_concatenate_to_one_shot(kind):
    model = make_model(ALWAYS)
    trace = frames_of(4 * C)
    ref_idx, ref_frames = drain_of(kind, model, trace)
    assert len(ref_idx) > 0

    # same trace, drained after every chunk (plus immediate re-drains,
    # which are empty) — concatenation must reproduce the one-shot drain
    if kind == "stream":
        r = StreamRunner(model, CFG, chunk_size=C, block_d=64,
                         control=CTL)
        drains = []
        for t in range(0, len(trace), C):
            r.process(trace[t:t + C])
            drains.append(r.drain_hp())
            drains.append(r.drain_hp())          # empty, (0, H, W)
    elif kind == "fleet":
        r = FleetRunner(model, CFG, chunk_size=C, block_d=64, control=CTL)
        drains = []
        for t in range(0, len(trace), C):
            r.process(trace[None, t:t + C])
            drains.append(r.drain_hp()[0])
            drains.append(r.drain_hp()[0])
    else:
        svc = FleetService(model, CFG, n_slots=1, chunk_size=C,
                           block_d=64, control=CTL)
        svc.attach(0)
        drains = []
        for t in range(0, len(trace), C):
            svc.dispatch({0: trace[t:t + C]})
            svc.flush()
            drains.append(svc.drain_hp(0))
            drains.append(svc.drain_hp(0))
    assert any(len(i) == 0 for i, _ in drains)   # empties interleaved
    idx = np.concatenate([i for i, _ in drains])
    frames = np.concatenate([f for _, f in drains])
    np.testing.assert_array_equal(idx, ref_idx)
    np.testing.assert_array_equal(frames, ref_frames)
    # absolute, strictly increasing across drain boundaries
    assert (np.diff(idx) > 0).all()


def test_indices_stay_absolute_across_process_calls():
    model = make_model(ALWAYS)
    trace = frames_of(3 * C)
    r = StreamRunner(model, CFG, chunk_size=C, block_d=64, control=CTL)
    r.process(trace[:C])
    first, _ = r.drain_hp()
    r.process(trace[C:])
    second, _ = r.drain_hp()
    assert len(first) and len(second)
    assert second.min() >= C          # not restarted at 0 after a drain
    both = np.concatenate([first, second])
    assert (np.diff(both) > 0).all()


# ---------------------------------------------------------------------------
# drain → checkpoint → restore preserves exactly the undrained frames
# ---------------------------------------------------------------------------

def test_drain_checkpoint_restore_preserves_undrained(tmp_path):
    model = make_model(ALWAYS)
    trace = frames_of(4 * C)
    td = os.fspath(tmp_path)

    def build():
        return FleetService(model, CFG, n_slots=1, chunk_size=C,
                            block_d=64, control=CTL, ckpt_dir=td)

    svc = build()
    svc.attach(0)
    svc.dispatch({0: trace[0:C]})
    svc.dispatch({0: trace[C:2 * C]})
    svc.flush()
    taken_idx, _ = svc.drain_hp(0)        # drained BEFORE the snapshot
    assert len(taken_idx)
    svc.dispatch({0: trace[2 * C:3 * C]})  # undrained burst accumulates
    svc.dispatch({0: trace[3 * C:4 * C]})
    svc.checkpoint()
    svc.wait_ckpt()
    ref_idx, ref_frames = svc.drain_hp(0)
    assert len(ref_idx)

    svc2 = build()
    svc2.restore()
    got_idx, got_frames = svc2.drain_hp(0)
    np.testing.assert_array_equal(got_idx, ref_idx)     # only undrained
    np.testing.assert_array_equal(got_frames, ref_frames)
    assert not np.intersect1d(got_idx, taken_idx).size


# ---------------------------------------------------------------------------
# assemble_capture_log (one stream's log from its per-tick blocks)
# ---------------------------------------------------------------------------

def test_assemble_capture_log_empty_and_axis():
    log = assemble_capture_log([], [], lp_bits=4, control=CTL,
                               frame_pixels=64)
    assert log.sampled.shape == (0,) and log.hp_bits == CTL.hp_bits
    open_loop = assemble_capture_log([], [], lp_bits=None, control=None,
                                     frame_pixels=64)
    assert open_loop.gated.shape == (0,) and open_loop.hp_bits is None
    two = assemble_capture_log([np.ones(3, bool), np.ones(2, bool)],
                               [np.zeros(3, bool), np.zeros(2, bool)],
                               lp_bits=None, control=None,
                               frame_pixels=64)
    assert two.sampled.shape == (5,) and two.gated.shape == (5,)


# ---------------------------------------------------------------------------
# energy: explicit handling of a depth-less (open-loop) log
# ---------------------------------------------------------------------------

def _log(hp_bits):
    gated = np.zeros(10, bool)
    gated[3:5] = True
    return CaptureLog(sampled=np.ones(10, bool), gated=gated,
                      lp_bits=None, hp_bits=hp_bits, frame_pixels=64)


def test_missing_hp_bits_defaults_to_params_depths():
    p = energy.EnergyParams()
    open_loop = energy.from_capture_log(_log(None), p)
    closed = energy.from_capture_log(_log(p.adc_hp_bits), p)
    assert open_loop == closed            # the documented convention


def test_missing_hp_bits_error_mode():
    with pytest.raises(ValueError, match="hp_bits"):
        energy.from_capture_log(_log(None), on_missing_bits="error")
    energy.from_capture_log(_log(12), on_missing_bits="error")  # fine
    with pytest.raises(ValueError, match="on_missing_bits"):
        energy.from_capture_log(_log(12), on_missing_bits="zero")


def test_cascade_system_accounting():
    cost = energy.BackboneCost(flops=1e6, bytes=1e5, joules=1e-3)
    with pytest.raises(ValueError, match="hp_bits"):
        energy.cascade_system(_log(None), cost)
    duty = _log(12).gated.mean()
    casc = energy.cascade_system(_log(12), cost)
    always = energy.always_on_backbone(cost)
    assert casc.cloud == pytest.approx(duty * cost.joules)
    assert always.cloud == pytest.approx(cost.joules)
    assert casc.total < always.total      # sparse duty must win


# ---------------------------------------------------------------------------
# detector cell: bitwise batch invariance (the cascade's foundation)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def detector():
    cfg = configs.get_smoke("hubert-xlarge")
    params = steps.init_detector_params(jax.random.PRNGKey(7), cfg,
                                        frame_hw=HW, patch=8)
    return cfg, params


def test_detector_cell_bitwise_batch_invariant(detector):
    cfg, params = detector
    cell = steps.build_detector_cell(cfg, batch=3, frame_hw=HW, patch=8)
    step = jax.jit(cell.step_fn)
    batch = frames_of(3, seed=5)
    out = np.asarray(step(params, batch)[0])
    perm = [2, 0, 1]
    out_perm = np.asarray(step(params, batch[perm])[0])
    np.testing.assert_array_equal(out[perm], out_perm)
    # a row's logits don't depend on what it is co-batched with
    alone = np.asarray(step(params, np.stack(
        [batch[1], np.zeros(HW, np.float32), np.zeros(HW, np.float32)]))[0])
    np.testing.assert_array_equal(alone[0], out[1])


def test_detector_cell_validates(detector):
    cfg, _ = detector
    with pytest.raises(ValueError, match="divide"):
        steps.build_detector_cell(cfg, batch=2, frame_hw=(15, 16),
                                  patch=8)
    with pytest.raises(ValueError, match="embeds-in"):
        steps.build_detector_cell(configs.get_smoke("olmo-1b"), batch=2,
                                  frame_hw=HW, patch=8)


# ---------------------------------------------------------------------------
# CascadeService: gate feed → batched async backbone, bitwise + 1 compile
# ---------------------------------------------------------------------------

def test_cascade_matches_eager_across_ragged_drains(detector):
    cfg, params = detector
    casc = CascadeService(params, cfg, batch_size=4, frame_hw=HW)
    frames = frames_of(9, seed=6)
    casc.submit("a", np.arange(2), frames[:2])            # partial
    casc.submit("a", [], np.zeros((0, *HW), np.float32))  # empty drain
    casc.submit("b", np.arange(3), frames[2:5])           # fills batch 1
    casc.submit("a", 2 + np.arange(4), frames[5:])        # fills batch 2
    batches = casc.flush()                                # + padded tail
    assert casc.queued == 0
    assert sum(b.n_padded for b in batches) > 0           # tail padded
    served = np.concatenate([b.logits for b in batches])
    order = np.concatenate([b.frame_idx for b in batches])
    assert served.shape == (9, casc.n_out)
    np.testing.assert_array_equal(served, casc.eager(frames))
    assert casc.compile_count() == 1                      # never retraced
    # provenance survives batching: (sid, absolute idx) per row
    sids = [s for b in batches for s in b.sids]
    assert sids == ["a", "a", "b", "b", "b", "a", "a", "a", "a"]
    np.testing.assert_array_equal(order,
                                  [0, 1, 0, 1, 2, 2, 3, 4, 5])


def test_cascade_pump_closes_the_loop(detector):
    """FleetService gate → pump → backbone: the full paper loop, with
    results keyed by (sensor, the gate's absolute frame index)."""
    cfg, params = detector
    model = make_model(ALWAYS)
    svc = FleetService(model, CFG, n_slots=1, chunk_size=C, block_d=64,
                       control=CTL)
    svc.attach("radar")
    casc = CascadeService(params, cfg, batch_size=4, frame_hw=HW)
    trace = frames_of(3 * C, seed=8)
    taken = 0
    for t in range(0, len(trace), C):
        svc.dispatch({"radar": trace[t:t + C]})
        svc.flush()
        taken += casc.pump(svc)
    assert taken
    batches = casc.flush()
    assert {s for b in batches for s in b.sids} == {"radar"}
    got = {int(i): row for b in batches
           for i, row in zip(b.frame_idx, b.logits)}
    assert len(got) == taken
    idx = np.array(sorted(got))
    # the HP captures are the raw frames at hp_bits
    eager = casc.eager(np.asarray(adc.quantize(trace[idx], CTL.hp_bits)))
    for j, i in enumerate(idx):
        np.testing.assert_array_equal(got[i], eager[j])
    assert casc.compile_count() == 1


def test_cascade_rejects_mismatched_frames(detector):
    cfg, params = detector
    casc = CascadeService(params, cfg, batch_size=2, frame_hw=HW)
    with pytest.raises(ValueError, match="cascade"):
        casc.submit(0, [0], np.zeros((1, 8, 8), np.float32))
    with pytest.raises(ValueError, match="disagree"):
        casc.submit(0, [0, 1], np.zeros((1, *HW), np.float32))


# ---------------------------------------------------------------------------
# sanitizer-harness regression (repro.analysis.sanitize)
# ---------------------------------------------------------------------------

from repro.analysis import sanitize  # noqa: E402


def test_warm_cascade_batches_are_compile_clean(detector, compile_ledger):
    """Post-warmup cascade batches run entirely from the jit cache.

    The backbone compiles exactly once for the fixed ``(B, H, W)`` batch
    shape; every later launch — ragged submits included — must trigger
    zero fresh XLA compiles, and submitting *device* drains must not
    perform implicit transfers (host queueing is the waived, explicit
    admission boundary).
    """
    cfg, params = detector
    casc = CascadeService(params, cfg, batch_size=2, frame_hw=HW)
    casc.submit(0, [0, 1], frames_of(2, seed=1))         # warmup batch
    casc.flush()
    with compile_ledger.expect_no_compiles("warm cascade batches"), \
            sanitize.no_implicit_transfers(always=True):
        dev = jax.device_put(frames_of(2, seed=2))
        casc.submit(1, [2, 3], dev)                       # device drain
        casc.submit(0, [4], frames_of(1, seed=3))         # ragged tail
        got = casc.flush()
    assert sum(len(b.frame_idx) for b in got) == 3
