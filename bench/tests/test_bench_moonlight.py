"""Moonlight-16B-A3B as the cascade's detector, at its reference's tiny
sizes on the CPU: the served detector against ``bench/reference/
moonlight.py``, what the check holds it to, the routing counters and the
metrics that read them, and a whole run of the cell."""

import json
import time

import jax
import numpy as np
import pytest

from bench import run, spec
from bench.reference import moonlight
from bench.tests import tiny
from bench.tests.tiny import CPU_PEAKS

NAME = "cascade-moonlight.burst"
SEED = 2**37 + 3
HW = (32, 32)


@pytest.fixture(scope="module")
def cell():
    return tiny.tiny_cell(NAME)


def _detector(cell, **kw):
    from repro.configs.base import ModelConfig
    from repro.launch.cascade import CascadeService

    d = {**cell.config["detector"], **kw}
    g = {"frame_h": HW[0], "frame_w": HW[1]}
    params = moonlight.make_weights(jax.random.PRNGKey(11), g, d)
    cfg = ModelConfig(**{k: v for k, v in d.items()
                         if k in ModelConfig.__dataclass_fields__})
    casc = CascadeService(params, cfg, batch_size=d["batch"], frame_hw=HW,
                          patch=d["patch"], n_out=d["n_out"])
    return casc, params, d


def _frames(n, seed):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 1.5, (n, *HW)).astype(np.float32)


def _served(casc, frames):
    """Logits of ``frames`` through submit/flush, in order."""
    casc.submit(0, np.arange(len(frames)), frames)
    out = {int(i): row for b in casc.flush()
           for i, row in zip(b.frame_idx, b.logits)}
    return np.stack([out[i] for i in range(len(frames))])


def _noise_ratio(got, params, frames, d):
    """The check's ``logit_noise_ratio``: RMS |program - reference| over
    the RMS the reference itself moves between float32 and bfloat16."""
    ref = moonlight.logits(params, frames, d)
    noise = moonlight.logits(params, frames, d, mode="float32") - ref
    rms = lambda a: float(np.sqrt(np.mean(np.square(a))))
    return rms(got - ref) / rms(noise)


def test_served_detector_agrees_with_the_reference(cell):
    """Through CascadeService (ragged submits, a padded tail): float32
    weights and products agree with the float32 reference to 1e-4 of the
    logits' RMS (the order of float32 sums); the configuration's bfloat16
    stays under the check's ``logit_noise_ratio`` limit."""
    frames = _frames(10, 1)
    casc, params, d = _detector(cell, compute_dtype="float32",
                                param_dtype="float32")
    got = _served(casc, frames)
    want = moonlight.logits(params, frames, d, mode="float32")
    rms = float(np.sqrt(np.mean(np.square(want))))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * rms)
    casc, params, d = _detector(cell)
    got = _served(casc, frames)
    assert _noise_ratio(got, params, frames, d) < \
        cell.config["limits"]["logit_noise_ratio"]


def _plant(monkeypatch, fault):
    """A fault in the program's held-expert layer: the held experts' part
    dropped, each held pair run by the next held expert's weights, or the
    grouped matmul's group sizes rolled by one expert."""
    from repro.models import mlp

    if fault == "group_sizes_rolled":
        ragged_dot = jax.lax.ragged_dot
        monkeypatch.setattr(jax.lax, "ragged_dot", lambda x, w, g, **kw:
                            ragged_dot(x, w, jax.numpy.roll(g, 1), **kw))
        return
    held_apply = mlp.held_apply

    def faulty(params, x, cfg):
        p = dict(params)
        for k in ("w_gate", "w_up", "w_down"):
            p[k] = (jax.numpy.roll(p[k], 1, axis=0)
                    if fault == "experts_shifted_by_one" else 0 * p[k])
        return held_apply(p, x, cfg)

    monkeypatch.setattr(mlp, "held_apply", faulty)


@pytest.mark.parametrize("fault", ["float8_control", "bidirectional",
                                   "held_experts_dropped",
                                   "experts_shifted_by_one",
                                   "group_sizes_rolled"])
def test_the_check_fails_a_lower_precision_or_another_network(
        cell, fault, monkeypatch):
    frames = _frames(12, 2)
    if fault == "float8_control":
        casc, params, d = _detector(cell)
        got = moonlight.logits(params, frames, d, mode="float8")
    else:
        if fault == "bidirectional":
            casc, params, d = _detector(cell, causal=False)
        else:
            _plant(monkeypatch, fault)
            casc, params, d = _detector(cell)
        got = _served(casc, frames)
    assert _noise_ratio(got, params, frames, d) > \
        cell.config["limits"]["logit_noise_ratio"]


def test_a_frame_s_logits_do_not_depend_on_its_batch(cell):
    """The whole batch runs through the expert layers together: a frame's
    logits are the same alone among pad rows and among other frames
    (bitwise on the CPU)."""
    casc, params, d = _detector(cell)
    frames = _frames(d["batch"], 3)
    together = _served(casc, frames)
    for j in (0, d["batch"] - 1):
        alone = _served(casc, frames[j:j + 1])
        np.testing.assert_array_equal(alone[0], together[j])


def test_routing_counters_count_every_pair(cell):
    from repro.launch import telemetry

    casc, _, d = _detector(cell)
    telemetry.reset()
    _served(casc, _frames(2 * d["batch"] - 1, 4))
    c = telemetry.snapshot()["counters"]
    layers = d["n_layers"] - d["first_dense_layers"]
    tokens = (HW[0] // d["patch"]) * (HW[1] // d["patch"])
    assert c["cascade.routed_pairs"] == 2 * d["batch"] * tokens \
        * d["top_k"] * layers
    assert 0 < c["cascade.local_pairs"] <= c["cascade.local_pairs_peak"]
    assert c["cascade.local_pairs_peak"] <= c["cascade.routed_pairs"] \
        * d["n_experts"] // d["top_k"]


def _ctx(cell, window_s=2.0, ops=(), runs=3):
    d = cell.config["detector"]
    return {"detector": d, "config": cell.config, "gate": cell.config["gate"],
            "peaks": CPU_PEAKS, "chips": 1,
            "trace": {"window_s": window_s, "device_ops": list(ops),
                      "modules": {"jit(detector_step)": {
                          "count": runs, "seconds": 1.0}}}}


def test_expert_metrics_read_the_counters(cell, monkeypatch):
    from bench import program

    d = cell.config["detector"]
    layers = d["n_layers"] - d["first_dense_layers"]
    tokens = (HW[0] // d["patch"]) * (HW[1] // d["patch"])
    routed = 4 * d["batch"] * tokens * d["top_k"] * layers     # 4 batches
    counters = {"cascade.routed_pairs": routed,
                "cascade.local_pairs": routed // 8,
                "cascade.local_pairs_peak": routed // 4}
    monkeypatch.setattr(program, "counters", lambda: counters)
    read = lambda m, ctx: spec.metric_reader(m)(ctx)
    ctx = _ctx(cell, ops=[["ragged-dot-none", 0.5],
                          ["ragged-dot-none.1", 0.25],
                          ["ragged-dot-metadata", 0.01], ["fusion.3", 9.0]])
    assert read("expert_local_share.moonlight_fps", ctx) == 12.5
    assert read("expert_load_peak.moonlight_fps", ctx) == 2.0
    pairs = 3 * (routed // 8) / 4                  # 3 batches traced
    least = max(pairs * moonlight.expert_pair_flops(d) / CPU_PEAKS.bf16_flops,
                (pairs * moonlight.expert_pair_bytes(d) + 3 * layers
                 * moonlight.expert_layer_bytes(d)) / CPU_PEAKS.hbm_bytes)
    # two of the three projections listed: two thirds of the work
    assert read("expert_ffn_roofline.moonlight_fps", ctx) == \
        pytest.approx(least * 2 / 3 / 0.75 * 100.0)
    # no grouped-matmul op among the costliest, or no counters: nothing
    assert read("expert_ffn_roofline.moonlight_fps",
                _ctx(cell, ops=[["fusion.3", 9.0]])) is None
    counters.clear()
    assert read("expert_local_share.moonlight_fps", ctx) is None
    assert read("expert_ffn_roofline.moonlight_fps", ctx) is None


def test_the_configuration_states_its_cut():
    cfg = json.loads((spec.BENCH / "configs"
                      / "hs-cascade-moonlight-16b-a3b.json").read_text())
    d = cfg["detector"]
    assert (d["d_model"], d["n_heads"], d["kv_lora_rank"],
            d["qk_nope_head_dim"], d["qk_rope_head_dim"], d["v_head_dim"],
            d["d_ff"], d["dense_d_ff"], d["router_experts"], d["top_k"],
            d["n_shared_experts"], d["n_layers"], d["vocab"]) == \
        (2048, 16, 512, 128, 64, 128, 1408, 11264, 64, 6, 2, 27, 163840)
    assert d["n_experts"] == cfg["n_routed_experts"] == 8
    assert cfg["published_n_routed_experts"] == 64
    assert cfg["reduced"] == ["n_routed_experts"]
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == cfg["name"])
    assert entry["reduced"] == ["n_routed_experts"]
    assert moonlight.frame_flops(cfg["gate"], d) == \
        pytest.approx(0.5629e12, rel=1e-4)


def test_the_cell_runs_correct_end_to_end(cell):
    line = run.run_cell(cell, SEED, 1.0, False, jax.devices(), CPU_PEAKS,
                        time.perf_counter())
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0
    assert "logit_noise_ratio" in line["checks"]
    assert set(line["metrics"]) == {"cascade_frames_per_s", "setup_s"}
