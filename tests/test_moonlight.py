"""Moonlight-16B-A3B's blocks: latent attention, the dropless expert layer
over held experts, and the leading dense layer, against plain jnp
written here (float32 on the CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention, common, lm, mlp

jax.config.update("jax_platform_name", "cpu")

HI = jax.lax.Precision.HIGHEST
N_ROUTED, HELD, TOP_K, D, F = 16, 4, 3, 64, 32
held_apply = jax.jit(mlp.held_apply, static_argnums=2)


def _full_cfg(**kw) -> mlp.HeldExpertsConfig:
    base = dict(d_model=D, d_ff=F, n_held=N_ROUTED, n_routed=N_ROUTED,
                top_k=TOP_K, n_shared=2, scaling=2.446)
    return mlp.HeldExpertsConfig(**{**base, **kw})


@pytest.fixture(scope="module")
def layer():
    """Every expert of one layer, a random correction bias, and tokens."""
    cfg = _full_cfg()
    p = common.init_params(jax.random.PRNGKey(0), mlp.held_spec(cfg))
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(1),
                                                (N_ROUTED,))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, D))
    return cfg, p, x


def _shard(p, lo, n):
    return {**p, **{k: p[k][lo:lo + n] for k in ("w_gate", "w_up", "w_down")}}


def _plain_layer(p, x, cfg):
    """Every expert on every token, weighted by its routing weight."""
    xf = x.reshape(-1, D)
    logits = jnp.dot(xf, p["router"], precision=HI)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + p["router_bias"], cfg.top_k)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / w.sum(-1, keepdims=True) * cfg.scaling
    dense = jnp.zeros_like(s).at[jnp.arange(xf.shape[0])[:, None], idx].set(w)

    def swiglu(q, g, u, dn):
        h = jax.nn.silu(jnp.dot(q, g, precision=HI)) * jnp.dot(q, u,
                                                               precision=HI)
        return jnp.dot(h, dn, precision=HI)

    y = sum(dense[:, j, None] * swiglu(xf, p["w_gate"][j], p["w_up"][j],
                                       p["w_down"][j])
            for j in range(p["w_gate"].shape[0]))
    sh = p["shared"]
    y = y + swiglu(xf, sh["w_gate"], sh["w_up"], sh["w_down"])
    return y.reshape(x.shape), dense


def test_held_shards_add_up_to_the_uncut_layer(layer):
    """Each of 4 shards holds 4 of 16 experts: their partial outputs, with
    the shared experts counted once, add up to the whole layer."""
    cfg, p, x = layer
    want, dense = _plain_layer(p, x, cfg)
    shared = mlp.apply(p["shared"], x, mlp.MLPConfig(D, 2 * F))
    total, pairs = jnp.zeros_like(x), 0
    for lo in range(0, N_ROUTED, HELD):
        scfg = _full_cfg(n_held=HELD, offset=lo)
        out, loads = held_apply(_shard(p, lo, HELD), x, scfg)
        total = total + out - shared
        pairs += int(loads.sum())
        chosen = np.asarray(dense[:, lo:lo + HELD] > 0).sum(0)
        np.testing.assert_array_equal(np.asarray(loads), chosen)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert pairs == x.shape[0] * x.shape[1] * TOP_K


def test_skewed_routing_drops_no_token(layer):
    """A bias that sends every token to held experts 0-2: all of the pairs
    land on three of the four held experts, and every one is computed."""
    cfg, p, x = layer
    skew = {**p, "router_bias": jnp.zeros(N_ROUTED).at[:TOP_K].set(10.0)}
    scfg = _full_cfg(n_held=HELD)
    out, loads = held_apply(_shard(skew, 0, HELD), x, scfg)
    n = x.shape[0] * x.shape[1]
    np.testing.assert_array_equal(np.asarray(loads), [n, n, n, 0])
    want, _ = _plain_layer(_shard(skew, 0, HELD), x, scfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _rope_interleaved(x, theta):
    """Pairs (2i, 2i+1) turned as complex numbers, kept interleaved."""
    s, n = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, n, 2) / n)
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(
        1j * np.arange(s)[:, None] * freqs)[None, :, None, :]
    return np.stack([z.real, z.imag], -1).reshape(x.shape)


@pytest.mark.parametrize("causal", [True, False])
def test_latent_attention_matches_a_plain_forward(causal):
    cfg = attention.MLAConfig(d_model=D, n_heads=4, kv_lora_rank=16,
                              qk_nope_head_dim=8, qk_rope_head_dim=8,
                              v_head_dim=12, causal=causal, rope_theta=5e4)
    p = common.init_params(jax.random.PRNGKey(3), attention.mla_spec(cfg))
    p["kv_norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(4), (16,))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 10, D))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(attention.mla_full, static_argnums=2)(p, x, cfg)
    pn = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    xn = np.asarray(x, np.float64)
    q = np.einsum("bsd,dhk->bshk", xn, pn["wq"])
    ckv = xn @ pn["wkv_a"]
    lat = ckv[..., :16]
    lat = lat / np.sqrt((lat ** 2).mean(-1, keepdims=True) + 1e-6) \
        * pn["kv_norm"]["scale"]
    kv = np.einsum("bsr,rhk->bshk", lat, pn["wkv_b"])
    q_pe = _rope_interleaved(q[..., 8:], 5e4)
    k_pe = _rope_interleaved(ckv[:, :, None, 16:], 5e4)[:, :, 0]
    s = (np.einsum("bqhk,bshk->bhqs", q[..., :8], kv[..., :8])
         + np.einsum("bqhk,bsk->bhqs", q_pe, k_pe)) / np.sqrt(16)
    if causal:
        s = np.where(np.tril(np.ones((10, 10), bool)), s, -np.inf)
    a = np.exp(s - s.max(-1, keepdims=True))
    a = a / a.sum(-1, keepdims=True)
    o = np.einsum("bhqs,bshk->bqhk", a, kv[..., 8:])
    want = np.einsum("bshk,hkd->bsd", o, pn["wo"])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_the_leading_dense_layer_runs_before_the_expert_stack():
    cfg = configs.get_smoke("moonlight-16b-a3b")
    model = lm.build(cfg)
    spec = model.spec()
    assert len(spec["dense_layers"]) == cfg.first_dense_layers == 1
    assert spec["dense_layers"][0]["mlp"]["w_up"].shape == (64, 128)
    assert spec["layers"]["experts"]["w_up"].shape == (2, 4, 64, 32)
    assert spec["layers"]["experts"]["router"].shape == (2, 64, 16)
    params = model.init(jax.random.PRNGKey(6))
    batch = lm.Batch(tokens=jax.random.randint(jax.random.PRNGKey(7), (2, 8),
                                               0, cfg.vocab),
                     labels=jnp.zeros((2, 8), jnp.int32))
    forward = jax.jit(model.forward)
    full, _ = forward(params, batch)
    last, loads = jax.jit(model.last_logits)(params, batch)
    np.testing.assert_array_equal(np.asarray(last[:, 0]),
                                  np.asarray(full[:, -1]))
    assert loads.shape == (2, 4) and int(loads.sum()) <= 2 * 8 * 3
    # without the dense layer the output changes: it ran
    params["dense_layers"][0]["mlp"]["w_down"] *= 0
    bare, _ = forward(params, batch)
    assert not np.allclose(np.asarray(bare), np.asarray(full))


def test_the_configuration_keeps_every_published_width():
    cfg = configs.get_config("moonlight-16b-a3b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab) == \
        (27, 2048, 16, 163840)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.d_ff, cfg.dense_d_ff, cfg.first_dense_layers) == \
        (1408, 11264, 1)
    assert (cfg.router_experts, cfg.n_experts, cfg.expert_offset,
            cfg.top_k, cfg.n_shared_experts) == (64, 8, 0, 6, 2)
    assert (cfg.routed_scaling_factor, cfg.norm_eps, cfg.rope_theta) == \
        (2.446, 1e-5, 50000.0)
    # one chip's share (8 of 64 experts), the patch embedder in place of
    # the token embedding: 3.029 B parameters
    n = common.spec_param_count(lm.build(cfg.replace(embeds_in=True)).spec())
    assert 3.02e9 < n < 3.04e9
