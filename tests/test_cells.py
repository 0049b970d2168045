"""Cell builders: every (arch x shape) constructs specs + shardings.

No compilation (that's the dry-run's job) — this guards the construction
path: abstract args, sharding trees, decode-state specs, skip rules.
Runs on a 1x1 mesh with the production axis names, so every rules code
path executes.
"""

import jax
import pytest

from repro import configs
from repro.configs.base import applicable_shapes
from repro.launch import steps
from repro.launch.mesh import make_mesh

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


ALL_CELLS = [
    (arch, shape_name)
    for arch in configs.ARCH_IDS
    for shape_name, sc in applicable_shapes(configs.get_config(arch)).items()
    if sc is not None
]


def test_cell_count_matches_assignment():
    # 40 assigned cells, 9 skipped by the assignment's own rules; plus
    # moonlight-16b-a3b's train and prefill (latent attention: no decode)
    assert len(ALL_CELLS) == 33


@pytest.mark.parametrize("arch,shape_name", ALL_CELLS)
def test_build_cell(arch, shape_name, mesh):
    cfg = configs.get_config(arch)
    shape = configs.SHAPES[shape_name]
    cell = steps.build_cell(cfg, shape, mesh)
    # abstract args: pure ShapeDtypeStructs (no device allocation)
    for leaf in jax.tree.leaves(cell.abstract_args):
        assert isinstance(leaf, jax.ShapeDtypeStruct), type(leaf)
    # sharding trees structurally match the args where present
    n_args = len(cell.abstract_args)
    assert len(cell.in_shardings) == n_args


@pytest.mark.parametrize("arch,shape_name", ALL_CELLS)
def test_input_specs_shapes(arch, shape_name):
    cfg = configs.get_config(arch)
    shape = configs.SHAPES[shape_name]
    args = steps.input_specs(cfg, shape)
    if shape.kind == "train":
        params, opt, batch = args
        assert batch.labels.shape == (shape.global_batch, shape.seq_len)
    elif shape.kind == "prefill":
        params, batch = args
        assert batch.labels.shape == (shape.global_batch, shape.seq_len)
    else:
        params, state, db = args
        assert db.tokens.shape == (shape.global_batch, 1)
        # decode state exists and carries the full cache length somewhere
        leaves = jax.tree.leaves(
            state, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        assert leaves, arch
        if cfg.family in ("dense", "moe", "vlm"):
            assert any(shape.seq_len in leaf.shape for leaf in leaves), \
                "KV cache must span the assigned context length"


def test_encoder_has_no_decode_cell():
    cfg = configs.get_config("hubert-xlarge")
    with pytest.raises(ValueError):
        steps.input_specs(cfg, configs.SHAPES["decode_32k"])
