"""The check fails a broken timed path, and fails the control.

Each test drives a small cell through everything a run does after its look
for the chips, with one fault planted in the program underneath, and sees
``correct`` come out false. The faults are those these cells can have: an
answer altered where it is produced (a score, a high-precision frame, a
logit), half of the batch left out, and a step that returns its carried
state unchanged. (No cell here spans chips, so there is no exchange
between chips to leave out.)
"""

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, run
from bench.driver import Session
from bench.tests.tiny import CPU_PEAKS, tiny_cell

SEED = 2**35 + 11


def _correct(name):
    line = run.run_cell(tiny_cell(name), SEED, 1.0, False, jax.devices(),
                        CPU_PEAKS, time.perf_counter())
    return line["correct"], line["checks"]


def _wrap_finish(monkeypatch, edit):
    from repro.launch.serve import FleetService

    orig = FleetService._finish

    def finish(self, rec):
        chunk = orig(self, rec)
        edit(chunk)
        return chunk

    monkeypatch.setattr(FleetService, "_finish", finish)


def test_a_score_altered_where_it_is_produced(monkeypatch):
    def edit(chunk):
        s, f, g = chunk.outputs[0]
        chunk.outputs[0] = (s + np.float32(1e-3), f, g)

    _wrap_finish(monkeypatch, edit)
    ok, checks = _correct("radar-f32.saturate")
    assert not ok and checks[_score_number(checks)]["value"] > 1e-4


def test_half_of_the_batch_left_out(monkeypatch):
    def edit(chunk):
        for sid in list(chunk.outputs)[::2]:
            s, f, g = chunk.outputs[sid]
            chunk.outputs[sid] = (np.zeros_like(s), np.zeros_like(f),
                                  np.zeros_like(g))

    _wrap_finish(monkeypatch, edit)
    ok, _ = _correct("radar-f32.saturate")
    assert not ok


@pytest.mark.parametrize("name", ["radar-f32.saturate", "radar-f32.open60"])
def test_a_step_that_returns_its_state_unchanged(monkeypatch, name):
    from repro.sensing import fleet

    orig = fleet._build_step

    def build(*a, **kw):
        step = orig(*a, **kw)

        def frozen(frames, state, *rest, **kws):
            s, f, g, smp, new = step(frames, state, *rest, **kws)
            return s, f, g, smp, new.__class__(
                class_hvs=new.class_hvs, holds=jnp.zeros_like(new.holds),
                phases=jnp.zeros_like(new.phases), frame_idx=new.frame_idx)

        frozen._cache_size = lambda: 0
        return frozen

    monkeypatch.setattr(fleet, "_build_step", build)
    ok, checks = _correct(name)
    assert not ok and checks["decision_mismatch"]["value"] > 0


def test_a_high_precision_frame_altered(monkeypatch):
    from repro.launch.serve import FleetService

    orig = FleetService.drain_hp

    def drain(self, sid):
        idx, frames = orig(self, sid)
        return idx, frames + np.float32(0.01)

    monkeypatch.setattr(FleetService, "drain_hp", drain)
    ok, checks = _correct("radar-f32.open60")
    assert not ok and checks["hp_gap"]["value"] > 1e-3


def test_a_logit_altered_where_it_is_produced(monkeypatch):
    from repro.launch.cascade import CascadeService

    orig = CascadeService._finish

    def finish(self, rec):
        batch = orig(self, rec)
        return dataclasses.replace(batch, logits=batch.logits + 1.0)

    monkeypatch.setattr(CascadeService, "_finish", finish)
    ok, checks = _correct("cascade-hubert.burst")
    assert not ok


def test_half_of_a_detector_batch_left_out(monkeypatch):
    from repro.launch.cascade import CascadeService

    orig = CascadeService._finish

    def finish(self, rec):
        batch = orig(self, rec)
        logits = np.array(batch.logits)
        logits[::2] = 0.0
        return dataclasses.replace(batch, logits=logits)

    monkeypatch.setattr(CascadeService, "_finish", finish)
    ok, _ = _correct("cascade-hubert.burst")
    assert not ok


def _score_number(checks):
    return "score_gap" if "score_gap" in checks else "score_rms"


@functools.lru_cache(maxsize=None)
def _readings(name):
    """(program readings, control readings) of one small run."""
    s = Session(tiny_cell(name), SEED)
    s.build()
    s.run(1.0, None)
    s.free_program()
    out = check.program_outputs(s)
    ref = check.Reference(s, out.scores.shape[1])
    keys = check.logit_sample(s, out) if s.d is not None else []
    ref_out = ref.outputs(keys)
    prog, _ = check.numbers(s, out, ref, ref_out)
    ctl, _ = check.numbers(s, check.control_outputs(s, ref, keys), ref,
                           ref_out)
    return prog, ctl, s.cell.config["limits"]


@pytest.mark.parametrize("name", ["radar-f32.saturate", "radar-f32.open60",
                                  "cascade-hubert.burst"])
def test_the_control_reads_above_the_program(name):
    """The reference one precision down, in the program's place, reads at
    least three times what the program reads on a number that the cell
    compares, and the program passes every limit."""
    prog, ctl, limits = _readings(name)
    assert check.judge(limits, prog)[0]
    compared = [k for k in limits if k in prog and prog[k] > 0]
    assert any(ctl[k] >= 3 * prog[k] for k in compared), (prog, ctl)
