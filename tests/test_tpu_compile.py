"""The served kernels compile for a TPU v5e at the paper's widths.

Interpret mode (every other kernel test) cannot see what the TPU's kernel
compiler refuses: unaligned tiles, unsupported operand types, more fast
memory than a kernel may use. These tests compile — without a chip, for
a described v5e topology — the scoring kernel of one gate chunk at the
paper's operating point (128x128 frames, 96x96 fragments, stride 8,
D=5000) in every precision the fleet serves, the float32 kernel on
frames two band chunks wide, the whole gate step that the serving
runtime builds for a 16-sensor tick, and the hubert-xlarge detector step
at its published width. Nothing runs; a compile that passes is not a
chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers each
import every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import hubert_xlarge, hypersense as hs_config
from repro.core import encoding
from repro.core.hypersense import HyperSenseModel
from repro.core.sensor_control import CaptureConfig, ControllerConfig
from repro.kernels import ops as kops
from repro.kernels import sliding_scores as ss
from repro.kernels import sliding_scores_int as ssi
from repro.launch import steps
from repro.launch.serve import FleetService

CFG = hs_config.config()
CHUNK = 32                      # frames in one gate step of one sensor


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _paper_tiles(precision: str, W: int = CFG.frame_w):
    """Abstract kernel tiles of a paper-width gate (shapes only)."""
    def build():
        B0, b = encoding.make_perm_base_rows(jax.random.PRNGKey(0),
                                             CFG.fragment, CFG.dim)
        chvs = jnp.ones((2, CFG.dim), jnp.float32)
        kw = dict(W=W, w=CFG.fragment, stride=CFG.stride, block_d=512)
        if precision == "float32":
            return ss.precompute_tiles(B0, b, chvs, **kw)
        return ssi.precompute_tiles_int(
            B0, b, chvs, mode="binary" if precision == "binary" else "int8",
            **kw)
    return jax.eval_shape(build)


@pytest.mark.parametrize("precision", ["float32", "int8", "int4", "binary"])
def test_gate_kernel_compiles_at_paper_width(one_chip, precision):
    tiles = _on(one_chip, _paper_tiles(precision))
    assert tiles.geom.block_d == 512 and tiles.cpos_t.shape[0] == 10
    H, W = CFG.frame_h, CFG.frame_w
    kw = dict(h=CFG.fragment, w=CFG.fragment, stride=CFG.stride)
    if precision == "float32":
        frames = jax.ShapeDtypeStruct((CHUNK, H, W), jnp.float32,
                                      sharding=one_chip)
        fn = lambda f, t: ss.fragment_scores_batch(f, t, **kw)
    else:
        packed = precision == "int4"
        frames = jax.ShapeDtypeStruct((CHUNK, H, W // 2 if packed else W),
                                      jnp.uint8, sharding=one_chip)
        fn = lambda f, t: ssi.fragment_scores_batch_int(f, t, packed=packed,
                                                        **kw)
    compiled = jax.jit(fn).lower(frames, tiles).compile()
    assert "tpu_custom_call" in compiled.as_text()
    my = (H - CFG.fragment) // CFG.stride + 1
    assert compiled.out_info.shape == (CHUNK, my, my)


def test_float_kernel_compiles_two_chunks_wide(one_chip):
    """Frames 256 columns wide: two band chunks, each aligned by one
    strided lane rotate on a lane-aligned product, which Mosaic accepts
    only at a lane-multiple width; interpret mode cannot tell."""
    H, W = CFG.frame_h, 2 * CFG.frame_w
    assert ss.strided_alignment(W)
    tiles = _on(one_chip, _paper_tiles("float32", W))
    frames = jax.ShapeDtypeStruct((CHUNK, H, W), jnp.float32,
                                  sharding=one_chip)
    kw = dict(h=CFG.fragment, w=CFG.fragment, stride=CFG.stride)
    compiled = jax.jit(lambda f, t: ss.fragment_scores_batch(f, t, **kw)
                       ).lower(frames, tiles).compile()
    assert "tpu_custom_call" in compiled.as_text()
    my = (H - CFG.fragment) // CFG.stride + 1
    mx = (W - CFG.fragment) // CFG.stride + 1
    assert compiled.out_info.shape == (CHUNK, my, mx)


@pytest.mark.parametrize("capture", [False, True])
def test_served_gate_step_compiles_and_rolls_its_state(one_chip, monkeypatch,
                                                       capture):
    """The step the service owns — every slot parked by its mask, the
    carried state donated — compiled for a 16-sensor tick of paper-size
    frames, with and without the closed capture loop."""
    S, H, W = 16, CFG.frame_h, CFG.frame_w
    B0, b = encoding.make_perm_base_rows(jax.random.PRNGKey(0),
                                         CFG.fragment, CFG.dim)
    model = HyperSenseModel(jnp.ones((2, CFG.dim), jnp.float32), B0, b,
                            CFG.fragment, CFG.fragment, CFG.stride,
                            t_score=0.0, t_detection=0)
    svc = FleetService(model, ControllerConfig(10.0, 60.0, 6), n_slots=S,
                       chunk_size=CHUNK, backend="pallas", adc_bits=4,
                       control=CaptureConfig(hp_bits=12) if capture else None)
    step, tiles = svc._ensure_step(W)
    # the kernels pick interpret mode from the default backend (the CPU
    # here); the described chip gets the compiled kernel
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    args = (jnp.zeros((S, CHUNK, H, W), jnp.float32), svc._state, B0, b,
            tiles, jnp.float32(0.0), jnp.int32(CHUNK),
            jnp.zeros((S, CHUNK), jnp.int32), jnp.ones((S,), bool))
    compiled = step.func.lower(*_on(one_chip, args),
                               **step.keywords).compile()
    assert "tpu_custom_call" in compiled.as_text()
    scores, fired, gated, sampled, _ = compiled.out_info
    assert scores.shape == fired.shape == gated.shape == sampled.shape \
        == (S, CHUNK)
    # the donated state is aliased into the new state, not reallocated
    assert compiled.memory_analysis().alias_size_in_bytes > 0


def test_detector_step_compiles_at_full_width(one_chip):
    mcfg = hubert_xlarge.config()
    assert (mcfg.n_layers, mcfg.d_model, mcfg.n_heads, mcfg.d_ff) == \
        (48, 1280, 16, 5120)
    cell = steps.build_detector_cell(mcfg, batch=8,
                                     frame_hw=(CFG.frame_h, CFG.frame_w),
                                     patch=8)
    compiled = jax.jit(cell.step_fn).lower(
        *_on(one_chip, cell.abstract_args)).compile()
    mem = compiled.memory_analysis()
    print(f"hubert-xlarge detector step, batch 8: {mem}")
    # params + temporaries must sit in one v5e chip's 16 GB of HBM
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
