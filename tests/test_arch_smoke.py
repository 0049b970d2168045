"""Per-architecture smoke tests (assignment deliverable f).

For every assigned architecture: instantiate the REDUCED same-family
config, run one forward + one train step + (where applicable) one decode
step on CPU, assert output shapes and absence of NaNs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import applicable_shapes
from repro.models import lm
from repro.train import optim

jax.config.update("jax_platform_name", "cpu")

ARCHS = configs.ARCH_IDS


def make_batch(cfg, key, batch=2, seq=16):
    ks = jax.random.split(key, 3)
    tokens = jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab)
    labels = jax.random.randint(ks[1], (batch, seq), 0, cfg.vocab)
    embeds = None
    if cfg.embeds_in:
        tokens = None
        embeds = jax.random.normal(ks[2], (batch, seq, cfg.d_model))
    elif cfg.family == "vlm":
        embeds = jax.random.normal(
            ks[2], (batch, cfg.n_image_tokens, cfg.d_model))
    return lm.Batch(tokens=tokens, labels=labels, embeds=embeds)


@pytest.fixture(scope="module")
def built():
    """Cache (model, params) per arch across tests in this module."""
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = configs.get_smoke(arch)
            model = lm.build(cfg)
            params = model.init(jax.random.PRNGKey(0))
            cache[arch] = (cfg, model, params)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch, built):
    cfg, model, params = built(arch)
    batch = make_batch(cfg, jax.random.PRNGKey(1))
    logits, aux = model.forward(params, batch)
    assert logits.shape == (2, 16, cfg.vocab), logits.shape
    assert bool(jnp.all(jnp.isfinite(logits))), f"{arch}: NaN in logits"
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step(arch, built):
    cfg, model, params = built(arch)
    batch = make_batch(cfg, jax.random.PRNGKey(2))
    opt = optim.AdamW(lr=1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optim.apply_updates(params, updates), opt_state, loss

    params2, opt_state, loss1 = step(params, opt_state, batch)
    _, _, loss2 = step(params2, opt_state, batch)
    assert bool(jnp.isfinite(loss1)) and bool(jnp.isfinite(loss2)), arch
    # loss should be near ln(vocab) initially and decrease on the same batch
    assert float(loss2) < float(loss1) + 0.1, (arch, loss1, loss2)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step(arch, built):
    cfg, model, params = built(arch)
    if applicable_shapes(cfg)["decode_32k"] is None:
        pytest.skip("encoder-only or latent-attention arch: no decode step")
    state = model.init_decode_state(batch=2, max_seq=8)
    tok = jnp.array([[3], [5]], jnp.int32)
    logits, state = model.decode_step(
        params, state, lm.DecodeBatch(tokens=tok, index=jnp.int32(0)))
    assert logits.shape == (2, 1, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits))), arch
    logits, _ = model.decode_step(
        params, state, lm.DecodeBatch(tokens=tok, index=jnp.int32(1)))
    assert bool(jnp.all(jnp.isfinite(logits))), arch


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "zamba2-1.2b",
                                  "xlstm-350m", "qwen3-moe-235b-a22b"])
def test_decode_matches_forward(arch, built):
    """Prefill logits at position t == decode logits after t tokens.

    MoE capacity is lifted so no tokens drop: capacity-based dropping is a
    train-time batch effect that decode (one token at a time) cannot see.
    """
    cfg, model, params = built(arch)
    if cfg.n_experts:
        cfg = cfg.replace(capacity_factor=64.0)
        model = lm.build(cfg)
    seq = 8
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, seq), 0,
                                cfg.vocab)
    batch = lm.Batch(tokens=tokens,
                     labels=jnp.zeros_like(tokens), embeds=None)
    full_logits, _ = model.forward(params, batch)

    state = model.init_decode_state(batch=2, max_seq=seq)
    # make recurrent conv states fp32 for exact parity in fp32 smoke configs
    state = jax.tree.map(lambda x: x.astype(jnp.float32)
                         if x.dtype == jnp.bfloat16 else x, state)
    outs = []
    for t in range(seq):
        logits, state = model.decode_step(
            params, state,
            lm.DecodeBatch(tokens=tokens[:, t:t + 1], index=jnp.int32(t)))
        outs.append(logits)
    dec_logits = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits),
                               np.asarray(full_logits), rtol=2e-2,
                               atol=2e-2)


def test_exact_configs_match_assignment():
    """The full configs carry the exact assigned hyperparameters."""
    expect = {
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
        "codeqwen1.5-7b": (32, 4096, 32, 32, 13440, 92416),
        "internlm2-1.8b": (24, 2048, 16, 8, 8192, 92544),
        "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
    }
    for arch, (L, d, h, kv, ff, v) in expect.items():
        cfg = configs.get_config(arch)
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.kv_heads,
               cfg.d_ff, cfg.vocab)
        assert got == (L, d, h, kv, ff, v), (arch, got)
    assert configs.get_config("zamba2-1.2b").ssm_state == 64
    assert configs.get_config("qwen3-moe-235b-a22b").n_experts == 128
    assert configs.get_config("qwen3-moe-235b-a22b").top_k == 8
    assert configs.get_config("grok-1-314b").n_experts == 8
    assert configs.get_config("grok-1-314b").top_k == 2


def test_applicable_shapes_skip_rules():
    from repro.configs.base import applicable_shapes
    enc = applicable_shapes(configs.get_config("hubert-xlarge"))
    assert enc["decode_32k"] is None and enc["long_500k"] is None
    dense = applicable_shapes(configs.get_config("deepseek-67b"))
    assert dense["long_500k"] is None and dense["decode_32k"] is not None
    hyb = applicable_shapes(configs.get_config("zamba2-1.2b"))
    assert hyb["long_500k"] is not None
    sm = applicable_shapes(configs.get_config("xlstm-350m"))
    assert sm["long_500k"] is not None
