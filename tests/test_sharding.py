"""Logical-axis sharding rules engine + cell builders (no big compiles)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed import roofline as rl
from repro.distributed import sharding as shlib
from repro.launch.mesh import make_mesh

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def mesh():
    # 1 real device: mesh (1, 1) exercises the rules code paths; axis
    # sizes of 1 accept any dim, so specs resolve like the big mesh.
    return make_mesh((1, 1), ("data", "model"))


def test_spec_for_basic(mesh):
    spec = shlib.spec_for((64, 128), ("embed", "mlp"), mesh)
    assert spec == P("data", "model")


def test_spec_for_drops_non_divisible():
    mesh = make_mesh((1, 1), ("data", "model"))
    # simulate divisibility drop via a fake 16-wide axis: use rules math
    # directly through _axis_for
    taken = set()
    big_mesh_shape = {"data": 16, "model": 16}

    class FakeMesh:
        shape = big_mesh_shape

    got = shlib._axis_for("mlp", dict(shlib.DEFAULT_RULES), FakeMesh(),
                          24, taken)   # 24 % 16 != 0
    assert got is None
    got = shlib._axis_for("mlp", dict(shlib.DEFAULT_RULES), FakeMesh(),
                          32, taken)
    assert got == ("model",)


def test_priority_resolution_kv_before_cache_seq():
    class FakeMesh:
        shape = {"data": 16, "model": 16}

    # kv divisible -> kv takes "model", cache_seq left unsharded
    spec = shlib.spec_for((128, 32768, 16, 128),
                          ("act_batch", "cache_seq", "act_kv_heads", None),
                          FakeMesh())
    assert spec[2] == "model" and spec[1] is None
    # kv NOT divisible -> cache_seq takes "model"
    spec = shlib.spec_for((128, 32768, 8, 128),
                          ("act_batch", "cache_seq", "act_kv_heads", None),
                          FakeMesh())
    assert spec[2] is None and spec[1] == "model"


def test_expert_cap_fallback():
    class FakeMesh:
        shape = {"data": 16, "model": 16}

    # 128 experts divide -> expert dim sharded
    spec = shlib.spec_for((128, 2048, 512),
                          ("act_expert", "act_expert_cap", None), FakeMesh())
    assert spec[0] == "model" and spec[1] is None
    # 8 experts don't -> capacity dim sharded instead
    spec = shlib.spec_for((8, 2048, 512),
                          ("act_expert", "act_expert_cap", None), FakeMesh())
    assert spec[0] is None and spec[1] == "model"


def test_no_mesh_is_noop():
    x = jnp.zeros((4, 4))
    y = shlib.shard(x, "act_batch", "act_seq")
    assert y is x or (y == x).all()


def test_use_mesh_context(mesh):
    assert shlib.current_mesh() is None
    with shlib.use_mesh(mesh):
        assert shlib.current_mesh() is mesh
        x = jnp.zeros((4, 8))
        shlib.shard(x, "act_batch", None)   # must not raise
    assert shlib.current_mesh() is None


# ---------------------------------------------------------------------------
# Roofline helpers
# ---------------------------------------------------------------------------

def test_collective_bytes_parser():
    hlo = """
  %ar = bf16[16,1024]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = f32[8,256]{1,0} all-gather(%y), dimensions={0}
  %cp = bf16[4,4]{1,0} collective-permute(%z)
  %ars = bf16[16,1024]{1,0} all-reduce-start(%x)
  %add = f32[8,256]{1,0} add(%a, %b)
"""
    got = rl.collective_bytes(hlo)
    assert got["all-reduce"] == 16 * 1024 * 2 * 2   # incl. -start
    assert got["all-gather"] == 8 * 256 * 4
    assert got["collective-permute"] == 4 * 4 * 2
    assert got["all-to-all"] == 0


def test_roofline_terms_and_bottleneck():
    r = rl.Roofline(arch="x", shape="train_4k", mesh="single", chips=256,
                    hlo_gflops=1e6, hlo_gbytes=1e3, coll_gbytes=10.0,
                    model_gflops=5e5)
    assert r.t_compute == pytest.approx(1e15 / (256 * rl.PEAK_FLOPS))
    assert r.t_collective == pytest.approx(10e9 / rl.ICI_BW)
    assert r.bottleneck in ("compute", "memory", "collective")
    assert 0 < r.useful_flop_ratio <= 1.0


def test_active_params_moe():
    cfg = configs.get_config("qwen3-moe-235b-a22b")
    from repro.models import common, lm
    n = common.spec_param_count(lm.build(cfg).spec())
    act = rl.active_params(cfg, n)
    assert act < n * 0.2     # top-8 of 128 experts -> ~a22b of 235b
    dense_cfg = configs.get_config("olmo-1b")
    n2 = common.spec_param_count(lm.build(dense_cfg).spec())
    assert rl.active_params(dense_cfg, n2) == n2


def test_param_counts_match_reported_sizes():
    """Total params should be in the ballpark the arch names claim."""
    from repro.models import common, lm
    expect = {"olmo-1b": (1.0e9, 1.6e9),
              "deepseek-67b": (60e9, 72e9),
              "grok-1-314b": (250e9, 340e9),
              "qwen3-moe-235b-a22b": (180e9, 260e9),
              "xlstm-350m": (0.25e9, 0.6e9),
              "internlm2-1.8b": (1.5e9, 2.2e9)}
    for arch, (lo, hi) in expect.items():
        n = common.spec_param_count(lm.build(configs.get_config(arch)
                                             ).spec())
        assert lo <= n <= hi, (arch, n)


# ---------------------------------------------------------------------------
# hyperdim axis: mesh_extent + the D-shard retile invariant
# ---------------------------------------------------------------------------

def test_hyperdim_rule_registered():
    """The "hyperdim" logical axis claims the model mesh axis — the rule
    the 2-D fleet mesh rides on."""
    assert shlib.DEFAULT_RULES["hyperdim"] == ("model",)


def test_mesh_extent_basic(mesh):
    axes, k = shlib.mesh_extent("hyperdim", mesh)
    assert axes == ("model",) and k == 1
    axes, k = shlib.mesh_extent("sensors", mesh)
    assert axes == ("data",) and k == 1


def test_mesh_extent_multiplies_axis_sizes():
    class FakeMesh:
        shape = {"data": 4, "model": 2}

    axes, k = shlib.mesh_extent("hyperdim", FakeMesh())
    assert axes == ("model",) and k == 2
    axes, k = shlib.mesh_extent("sensors", FakeMesh())
    assert axes == ("data",) and k == 4


def test_mesh_extent_ignores_divisibility():
    """Unlike spec_for, mesh_extent reports the raw extent: the fleet
    uses it to PAD the sensor axis, so divisibility must not zero it."""
    class FakeMesh:
        shape = {"data": 8, "model": 1}

    axes, k = shlib.mesh_extent("sensors", FakeMesh())
    assert axes == ("data",) and k == 8          # S=5 pads to 8, not drops


def test_mesh_extent_unknown_or_meshless():
    assert shlib.mesh_extent("no_such_axis",
                             make_mesh((1, 1), ("data", "model"))) \
        == ((), 1)
    assert shlib.mesh_extent("hyperdim", None) == ((), 1)

    class NoModelMesh:
        shape = {"data": 4}

    assert shlib.mesh_extent("hyperdim", NoModelMesh()) == ((), 1)


try:  # prefer the real library when installed (requirements-dev.txt)
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # fallback keeps these tests running without the dep
    from _hypothesis_fallback import hypothesis, st


@hypothesis.given(st.integers(1, 7), st.integers(0, 2 ** 31 - 1))
@hypothesis.settings(max_examples=20, deadline=None)
def test_retile_is_dshard_boundary_invariant(cut, seed):
    """Splitting the geometry's tile axis (the hyperdim shards) and
    retiling each piece reproduces the full retile bitwise: class tiles
    are a pure per-tile gather and the cosine norms come from the FULL
    class vector, so no D-shard boundary can perturb the scoring tiles.
    This is the invariant that lets the 2-D mesh replicate class_hvs and
    shard only the geometry."""
    import numpy as np

    from repro.kernels import sliding_scores as ss

    h, dim, W, w, stride, block_d = 6, 128, 24, 6, 3, 16
    key = jax.random.PRNGKey(seed)
    B0 = jax.random.normal(key, (h, dim))
    b = jax.random.uniform(jax.random.fold_in(key, 1), (dim,))
    chvs = jax.random.normal(jax.random.fold_in(key, 2), (2, dim))
    geom = ss.precompute_geometry(B0, b, W=W, w=w, stride=stride,
                                  block_d=block_d)
    n_dt = geom.slabs.shape[0]
    assert n_dt == dim // block_d == 8 and 1 <= cut < n_dt

    full = ss.retile_classes(geom, chvs)
    import dataclasses
    parts = []
    for sl in (slice(0, cut), slice(cut, n_dt)):
        shard = dataclasses.replace(geom, slabs=geom.slabs[sl],
                                    bias_t=geom.bias_t[sl],
                                    idx=geom.idx[sl])
        parts.append(ss.retile_classes(shard, chvs))
    np.testing.assert_array_equal(
        np.asarray(full.cpos_t),
        np.concatenate([np.asarray(p.cpos_t) for p in parts]))
    np.testing.assert_array_equal(
        np.asarray(full.cneg_t),
        np.concatenate([np.asarray(p.cneg_t) for p in parts]))
    for p in parts:     # norms are full-D: identical on every shard
        np.testing.assert_array_equal(np.asarray(full.cpos_norm),
                                      np.asarray(p.cpos_norm))
        np.testing.assert_array_equal(np.asarray(full.cneg_norm),
                                      np.asarray(p.cneg_norm))
