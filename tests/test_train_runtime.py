"""Training runtime: optimizer, checkpoint/restart, compression, loop."""

import os

try:  # prefer the real library when installed (requirements-dev.txt)
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # fallback keeps these tests running without the dep
    from _hypothesis_fallback import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.ckpt import checkpoint as ckpt
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.train import compress, loop as train_loop, optim

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    opt = optim.AdamW(lr=0.1, weight_decay=0.0)
    params = {"x": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"x": 2 * params["x"]}          # d/dx x^2
        updates, state = opt.update(grads, state, params)
        params = optim.apply_updates(params, updates)
    assert float(jnp.abs(params["x"]).max()) < 1e-2


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0), "b": jnp.full((4,), 10.0)}
    clipped = optim.clip_by_global_norm(g, 1.0)
    assert abs(float(optim.global_norm(clipped)) - 1.0) < 1e-5
    small = {"a": jnp.full((4,), 0.01), "b": jnp.full((4,), 0.01)}
    same = optim.clip_by_global_norm(small, 1.0)
    np.testing.assert_allclose(np.asarray(same["a"]),
                               np.asarray(small["a"]))


def test_warmup_cosine_schedule():
    sched = optim.warmup_cosine(1.0, 10, 100)
    assert float(sched(jnp.int32(0))) == 0.0
    assert abs(float(sched(jnp.int32(10))) - 1.0) < 1e-6
    assert float(sched(jnp.int32(100))) <= 0.11
    assert float(sched(jnp.int32(5))) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def _tree(key):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (8, 16)),
            "nested": {"b": jax.random.normal(k2, (4,)),
                       "step": jnp.int32(7)}}


def test_checkpoint_roundtrip(tmp_path):
    tree = _tree(jax.random.PRNGKey(0))
    d = str(tmp_path / "ck")
    ckpt.save(d, 10, tree, extra={"step": 10})
    restored, extra = ckpt.restore(d, tree)
    assert extra["step"] == 10
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b)), tree, restored)


def test_checkpoint_keep_k_and_latest(tmp_path):
    d = str(tmp_path / "ck")
    tree = _tree(jax.random.PRNGKey(1))
    for s in [1, 2, 3, 4, 5]:
        ckpt.save(d, s, tree, keep=2)
    steps = sorted(x for x in os.listdir(d) if x.startswith("step_"))
    assert len(steps) == 2
    assert ckpt.latest_step(d) == 5


def test_checkpoint_crash_safety(tmp_path):
    """A leftover .tmp dir from a crash must not corrupt restore."""
    d = str(tmp_path / "ck")
    tree = _tree(jax.random.PRNGKey(2))
    ckpt.save(d, 1, tree)
    os.makedirs(os.path.join(d, "step_0000000002.tmp"))  # simulated crash
    assert ckpt.latest_step(d) == 1
    restored, _ = ckpt.restore(d, tree)
    ckpt.save(d, 3, tree)          # gc cleans the orphan
    assert not any(x.endswith(".tmp") for x in os.listdir(d))


def test_async_checkpointer(tmp_path):
    d = str(tmp_path / "ck")
    saver = ckpt.AsyncCheckpointer(d, keep=2)
    tree = _tree(jax.random.PRNGKey(3))
    saver.save(1, tree, extra={"step": 1})
    saver.wait()
    assert ckpt.latest_step(d) == 1


def test_elastic_reshard_restore(tmp_path):
    """Restore onto a different mesh (1-device here, but via explicit
    NamedSharding) — the elastic-rescale path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    d = str(tmp_path / "ck")
    tree = _tree(jax.random.PRNGKey(4))
    ckpt.save(d, 1, tree)
    mesh = make_mesh((1,), ("data",))
    sh = jax.tree.map(lambda x: NamedSharding(mesh, P()), tree)
    restored, _ = ckpt.restore(d, tree, shardings=sh)
    assert restored["w"].sharding == NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

@hypothesis.given(st.integers(0, 2**16))
@hypothesis.settings(max_examples=10, deadline=None)
def test_compress_roundtrip_accuracy(seed):
    g = {"w": jax.random.normal(jax.random.PRNGKey(seed), (300,)),
         "b": jax.random.normal(jax.random.PRNGKey(seed + 1), (7, 13))}
    ef = compress.init_error_feedback(g)
    qg, ef2 = compress.compress_grads(g, ef)
    deq = compress.decompress_grads(qg, g)
    for k in g:
        err = np.abs(np.asarray(deq[k] - g[k]))
        scale = np.abs(np.asarray(g[k])).max()
        assert err.max() <= scale / 127.0 + 1e-6   # int8 quantization bound
        # error feedback carries exactly the quantization residual
        np.testing.assert_allclose(np.asarray(ef2[k]),
                                   np.asarray(g[k] - deq[k]), atol=1e-6)


def test_error_feedback_reduces_bias():
    """Mean of dequantized grads over steps converges to the true mean
    with EF (the residual is re-injected)."""
    g = {"w": jnp.full((64,), 0.101)}
    ef = compress.init_error_feedback(g)
    total = jnp.zeros((64,))
    for _ in range(50):
        qg, ef = compress.compress_grads(g, ef)
        total = total + compress.decompress_grads(qg, g)["w"]
    mean = total / 50
    np.testing.assert_allclose(np.asarray(mean), 0.101, rtol=1e-3)


def test_compression_ratio():
    g = {"w": jnp.zeros((10000,))}
    r = compress.compression_ratio(g)
    assert 0.25 <= r <= 0.30       # int8 + block scales ~ 0.27x of fp32


# ---------------------------------------------------------------------------
# Train loop: run, checkpoint, kill, resume
# ---------------------------------------------------------------------------

def test_train_loop_resume(tmp_path):
    cfg = configs.get_smoke("olmo-1b")
    model = lm.build(cfg)
    data = train_loop.synthetic_lm_data(cfg, batch=2, seq=16)
    tc = train_loop.TrainConfig(steps=6, ckpt_every=3, log_every=2,
                                ckpt_dir=str(tmp_path / "ck"), lr=1e-3)
    r1 = train_loop.train(model, data, tc)
    assert r1["step"] == 6
    assert ckpt.latest_step(tc.ckpt_dir) == 6

    # simulate failure + relaunch with more steps: resumes from 6
    tc2 = train_loop.TrainConfig(steps=8, ckpt_every=3, log_every=2,
                                 ckpt_dir=str(tmp_path / "ck"), lr=1e-3)
    data2 = train_loop.synthetic_lm_data(cfg, batch=2, seq=16, start_step=6)
    r2 = train_loop.train(model, data2, tc2)
    assert r2["step"] == 8


def test_train_loop_microbatched_matches_loss_scale(tmp_path):
    cfg = configs.get_smoke("internlm2-1.8b")
    model = lm.build(cfg)
    opt = optim.AdamW(lr=0.0)      # lr 0: params unchanged -> same loss
    params = model.init(jax.random.PRNGKey(0))
    data = train_loop.synthetic_lm_data(cfg, batch=4, seq=16)
    batch = next(data)
    s1 = train_loop.make_train_step(model, opt, microbatches=1)
    s2 = train_loop.make_train_step(model, opt, microbatches=2)
    _, _, m1 = s1(params, opt.init(params), batch)
    _, _, m2 = s2(params, opt.init(params), batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-3)
