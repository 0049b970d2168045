"""Golden regression fixtures: the paper-facing numbers, frozen.

Small seeded ``StreamRunner`` / ``FleetRunner`` scenarios with their full
outputs (scores, gate decisions, ``StreamStats``, energy totals) checked
into ``tests/golden/*.json``. A refactor that shifts any of these numbers
— however plausibly — fails here first and must regenerate the fixtures
*explicitly* (``pytest tests/test_golden.py --update-golden``), making the
change visible in review instead of silently drifting the reproduction.

Scores (all precisions — recorded rounded to 6 decimals) are compared
with a small float tolerance (``SCORE_ATOL``, covering cross-platform
BLAS reduction order); gate decisions and stats counts are compared
exactly, and every scenario asserts its scores sit ``DECISION_MARGIN``
clear of the firing threshold so jitter within tolerance can never flip
a recorded decision.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoding, hypersense
from repro.core.online import AdaptConfig
from repro.core.sensor_control import ControllerConfig, stats_from
from repro.launch.mesh import make_mesh
from repro.launch.replay import FleetRunner, StreamRunner
from repro.sensing import synthetic
from repro.sensing.fleet import fleet_report

jax.config.update("jax_platform_name", "cpu")

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"
SCORE_ATOL = 5e-5


def make_model(h=6, w=6, stride=3, D=128, t_score=-0.05, t_detection=2):
    B0, b = encoding.make_perm_base_rows(jax.random.PRNGKey(1), h, D)
    C = jax.random.normal(jax.random.PRNGKey(2), (2, D))
    return hypersense.HyperSenseModel(C, B0, b, h, w, stride,
                                      t_score=t_score,
                                      t_detection=t_detection)


def make_stream_inputs(n=17, seed=10):
    cfg = synthetic.RadarConfig(height=24, width=24)
    frames, _, labels = synthetic.make_dataset(
        jax.random.PRNGKey(seed), n, cfg)
    return frames, np.asarray(labels)


#: every recorded score must sit at least this clear of the firing
#: threshold, so platform-level float jitter (bounded by SCORE_ATOL,
#: itself far above observed cross-BLAS drift) can never flip a golden
#: gate decision — asserted for EVERY scenario at build time (i.e. on
#: each compare and each --update-golden). 5x SCORE_ATOL.
DECISION_MARGIN = 5 * SCORE_ATOL


def _assert_decision_margin(scores, t_score):
    margin = float(np.abs(np.asarray(scores) - t_score).min())
    assert margin > DECISION_MARGIN, (
        f"golden scenario has a score within {margin:.2e} of t_score — "
        f"platform jitter could flip a recorded gate decision; reseed or "
        f"move t_score")


def _stream_payload(scores, fired, gated, labels, t_score):
    _assert_decision_margin(scores, t_score)
    stats = stats_from(fired, gated, labels)
    return {
        "scores": [round(float(s), 6) for s in np.asarray(scores).ravel()],
        "fired": np.asarray(fired).ravel().astype(int).tolist(),
        "gated": np.asarray(gated).ravel().astype(int).tolist(),
        "stats": {
            "duty_cycle": round(float(stats.duty_cycle), 6),
            "missed_positive": round(float(stats.missed_positive), 6),
            "false_active": round(float(stats.false_active), 6),
        },
    }


def scenario_stream_frozen():
    """Frozen single stream, ADC in the loop, jnp backend."""
    frames, labels = make_stream_inputs()
    model = make_model()
    r = StreamRunner(model, ControllerConfig(hold_frames=2),
                     chunk_size=5, adc_bits=4)
    return _stream_payload(*r.process(frames), labels, model.t_score)


def scenario_stream_int8():
    """The int8 ADC-code datapath on the same stream."""
    frames, labels = make_stream_inputs()
    model = make_model()
    r = StreamRunner(model, ControllerConfig(hold_frames=2),
                     chunk_size=5, adc_bits=8, precision="int8")
    return _stream_payload(*r.process(frames), labels, model.t_score)


def scenario_stream_int4():
    """The packed int4 wire format (two codes per byte) on the same
    stream — pins the nibble pack/unpack round trip end to end."""
    frames, labels = make_stream_inputs()
    model = make_model()
    r = StreamRunner(model, ControllerConfig(hold_frames=2),
                     chunk_size=5, adc_bits=4, precision="int4")
    return _stream_payload(*r.process(frames), labels, model.t_score)


def scenario_stream_binary():
    """The bipolar binary gate (sign-quantized slabs AND class HVs) on
    the same stream — pins the +-1 datapath's scores and decisions."""
    frames, labels = make_stream_inputs()
    model = make_model()
    r = StreamRunner(model, ControllerConfig(hold_frames=2),
                     chunk_size=5, adc_bits=8, precision="binary")
    return _stream_payload(*r.process(frames), labels, model.t_score)


def scenario_stream_adaptive():
    """Label-feedback online learning (the mutable-model hot path)."""
    frames, labels = make_stream_inputs(seed=11)
    model = make_model()
    r = StreamRunner(model, ControllerConfig(hold_frames=2),
                     chunk_size=5,
                     adapt=AdaptConfig(mode="label", lr=0.5))
    out = r.process(frames, labels=labels)
    payload = _stream_payload(*out, labels, model.t_score)
    # the adapted classifier itself is part of the contract
    payload["class_hvs_checksum"] = round(
        float(jnp.sum(jnp.abs(r.class_hvs))), 4)
    return payload


def scenario_fleet():
    """Two-sensor fleet + the energy account billed from its duty cycle."""
    cfg = synthetic.RadarConfig(height=24, width=24)
    frames = jnp.stack([
        synthetic.make_dataset(jax.random.PRNGKey(20 + s), 11, cfg)[0]
        for s in range(2)])
    labels = np.stack([
        np.asarray(synthetic.make_dataset(jax.random.PRNGKey(20 + s), 11,
                                          cfg)[2])
        for s in range(2)])
    model = make_model()
    r = FleetRunner(model, ControllerConfig(hold_frames=1),
                    chunk_size=4, adc_bits=4)
    scores, fired, gated = r.process(frames)
    _assert_decision_margin(scores, model.t_score)
    rep = fleet_report(fired, gated, labels)
    return {
        "scores": [round(float(s), 6) for s in scores.ravel()],
        "fired": fired.ravel().astype(int).tolist(),
        "gated": gated.ravel().astype(int).tolist(),
        "duty_cycle": round(rep.duty_cycle, 6),
        "energy_total_j": round(rep.energy_total_j, 6),
        "total_saving": round(rep.total_saving, 6),
    }


def scenario_fleet_sharded():
    """Closed-loop control + shared adaptation + int8, on a 4x2
    (sensors x hyperdim) mesh with S=3 padding the 4-way sensor axis —
    the full 2-D shard_map datapath in one frozen fixture. Bitwise parity
    with the unsharded runner is pinned in test_parity_matrix.py; this
    pins the VALUES (and, via test_golden_fleet_sharded_replays_bitwise,
    replay determinism) against silent drift."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    from repro.core.sensor_control import CaptureConfig
    from repro.distributed import sharding as shlib

    cfg = synthetic.RadarConfig(height=24, width=24)
    sets = [synthetic.make_dataset(jax.random.PRNGKey(30 + s), 11, cfg)
            for s in range(3)]
    frames = jnp.stack([st[0] for st in sets])
    labels = np.stack([np.asarray(st[2]) for st in sets])
    model = make_model()
    with shlib.use_mesh(make_mesh((4, 2), ("data", "model"))):
        r = FleetRunner(model,
                        ControllerConfig(base_rate_hz=20.0,
                                         active_rate_hz=60.0,
                                         hold_frames=2),
                        chunk_size=4, backend="jnp", block_d=16,
                        adc_bits=8, precision="int8",
                        adapt=AdaptConfig(mode="label", lr=0.5,
                                          scope="shared"),
                        control=CaptureConfig())
        scores, fired, gated = r.process(frames, labels=labels)
    # the step must really have sharded BOTH axes — a fallback would
    # freeze fallback numbers into the fixture
    assert r.service._step_axes == (("data",), ("model",))
    _assert_decision_margin(scores, model.t_score)
    rep = fleet_report(fired, gated, labels, capture=r.capture_log)
    return {
        "scores": [round(float(s), 6) for s in scores.ravel()],
        "fired": fired.ravel().astype(int).tolist(),
        "gated": gated.ravel().astype(int).tolist(),
        "sampled": np.asarray(r.capture_log.sampled).ravel()
                     .astype(int).tolist(),
        "duty_cycle": round(rep.duty_cycle, 6),
        "energy_total_j": round(rep.energy_total_j, 6),
        "class_hvs_checksum": round(
            float(jnp.sum(jnp.abs(r.class_hvs))), 4),
    }


SCENARIOS = {
    "stream_frozen": scenario_stream_frozen,
    "stream_int8": scenario_stream_int8,
    "stream_int4": scenario_stream_int4,
    "stream_binary": scenario_stream_binary,
    "stream_adaptive": scenario_stream_adaptive,
    "fleet": scenario_fleet,
    "fleet_sharded": scenario_fleet_sharded,
}


def _assert_matches(got, want, path=""):
    """Recursive compare: exact for ints/bools/strings, atol for floats."""
    assert type(got) is type(want), f"{path}: {type(got)} vs {type(want)}"
    if isinstance(want, dict):
        assert got.keys() == want.keys(), f"{path}: keys differ"
        for k in want:
            _assert_matches(got[k], want[k], f"{path}.{k}")
    elif isinstance(want, list):
        assert len(got) == len(want), f"{path}: length differs"
        if want and isinstance(want[0], float):
            np.testing.assert_allclose(got, want, atol=SCORE_ATOL,
                                       err_msg=path)
        else:
            for i, (g, w) in enumerate(zip(got, want)):
                _assert_matches(g, w, f"{path}[{i}]")
    elif isinstance(want, float):
        assert got == pytest.approx(want, abs=SCORE_ATOL), path
    else:
        assert got == want, path


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_golden(name, request):
    path = GOLDEN_DIR / f"{name}.json"
    got = SCENARIOS[name]()
    if request.config.getoption("--update-golden"):
        GOLDEN_DIR.mkdir(exist_ok=True)
        path.write_text(json.dumps(got, indent=1) + "\n")
        pytest.skip(f"golden fixture {path.name} regenerated")
    assert path.exists(), (
        f"missing golden fixture {path} — run "
        f"pytest tests/test_golden.py --update-golden and review the diff")
    want = json.loads(path.read_text())
    _assert_matches(got, want, name)


def test_golden_fleet_sharded_replays_bitwise():
    """Two independent builds of the sharded-fleet scenario — fresh
    runners, fresh compiles — produce the IDENTICAL payload, float for
    float: the mesh datapath (collectives included) is deterministic, so
    the golden fixture is replayable, not a lucky snapshot."""
    a = scenario_fleet_sharded()
    b = scenario_fleet_sharded()
    assert a == b

