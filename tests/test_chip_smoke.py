"""CPU rehearsal of ``chip_smoke.py``: every phase at smoke size.

The script itself refuses to run without a TPU; these tests call its
phase functions directly, with the Pallas kernels in interpret mode, so a
broken argument, shape or control path shows up here before any chip
time is spent. The mesh phase needs four devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4`` or more).
"""

import dataclasses
import importlib.util
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.configs import hubert_xlarge, hypersense as hs_config
from repro.kernels import sliding_scores
from repro.launch.compile_cache import enable_compile_cache

jax.config.update("jax_platform_name", "cpu")

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
sys.modules["chip_smoke"] = chip_smoke
_spec.loader.exec_module(chip_smoke)

CFG = hs_config.smoke()          # 32x32 frames, fragment 8, stride 4, D=256
S, C, TICKS = 2, 8, 3


@pytest.fixture(scope="module")
def gate():
    model = chip_smoke.train_gate(CFG, seed=0)
    streams = chip_smoke.make_streams(CFG, S, TICKS * C, seed=10)
    return model, streams


def test_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_compile_cache_stays_where_the_environment_puts_it(monkeypatch,
                                                          tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path          # fixed, not per call
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("precision,adc_bits", [("float32", 4), ("int8", 8)])
def test_gate_phase_matches_jnp(gate, precision, adc_bits):
    model, streams = gate
    svc, res = chip_smoke.gate_phase(model, streams, precision=precision,
                                     adc_bits=adc_bits, chunk=C)
    assert res["dev"] <= chip_smoke.SCORE_TOL
    assert res["ticks"] == TICKS and res["frames"] == S * TICKS * C
    # interpret mode on the CPU: no compiled kernel in the step
    assert res["kernel"] is False
    assert svc.attached == tuple(range(S))


def test_cascade_phase_drains_the_gate(gate):
    model, streams = gate
    svc, _ = chip_smoke.gate_phase(model, streams, precision="float32",
                                   adc_bits=4, chunk=C)
    res = chip_smoke.cascade_phase(svc, hubert_xlarge.smoke(),
                                   frame_hw=(CFG.frame_h, CFG.frame_w),
                                   patch=8, batch=4, seed=20, max_eager=6)
    assert res["frames"] >= 1 and res["dev"] == 0.0
    assert res["batches"] == -(-res["frames"] // 4)


def test_compare_tolerates_only_near_threshold_flips():
    shape = (2, 1, 4)                                  # (ticks, S, C)
    want = chip_smoke.Served(None, np.zeros(shape), np.zeros(shape, bool),
                             np.zeros(shape, bool), 0.0, 0.0)
    got = chip_smoke.Served(None, np.zeros(shape), np.zeros(shape, bool),
                            np.zeros(shape, bool), 0.0, 0.0)
    got.fired[0, 0, 1] = True                          # reference score at t
    res = chip_smoke.compare(got, want, t_score=0.0)
    assert res["decisions"] == 1 and res["frames"] == 8
    with pytest.raises(AssertionError, match="away from t_score"):
        chip_smoke.compare(got, want, t_score=0.5)
    got.scores[1, 0, 3] = 1.0
    with pytest.raises(AssertionError, match="reference"):
        chip_smoke.compare(got, want, t_score=0.0)


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_"
                           "platform_device_count=4)")
@pytest.mark.parametrize("mesh_shape,dim,block_d",
                         [((4, 1), CFG.dim, 512), ((2, 2), 1024, 128)])
def test_mesh_phase_matches_one_device(gate, mesh_shape, dim, block_d):
    model, streams = gate
    if dim != CFG.dim:
        model = chip_smoke.random_gate(CFG, dim, seed=30)
    res = chip_smoke.mesh_phase(model, streams, mesh_shape, chunk=C,
                                block_d=block_d)
    assert res["bitwise"]
    assert (res["sensor_shards"], res["hyperdim_shards"]) == mesh_shape


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_"
                           "platform_device_count=4)")
@pytest.mark.parametrize("mesh_shape,dim", [((4, 1), 256), ((2, 2), 512)])
def test_mesh_phase_on_the_strided_alignment(mesh_shape, dim):
    """Frames a whole band chunk wide take the strided lane rotate; the
    sharded fleet on that path still equals one device bitwise."""
    cfg = dataclasses.replace(CFG, frame_h=64, frame_w=128, fragment=16,
                              stride=8)
    assert sliding_scores.strided_alignment(cfg.frame_w)
    model = chip_smoke.random_gate(cfg, dim, seed=30)
    streams = chip_smoke.make_streams(cfg, 4, 2 * C, seed=10)
    res = chip_smoke.mesh_phase(model, streams, mesh_shape, chunk=C,
                                block_d=128)
    assert res["bitwise"]
    assert (res["sensor_shards"], res["hyperdim_shards"]) == mesh_shape
