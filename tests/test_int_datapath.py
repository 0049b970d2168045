"""Integer ADC-code datapath: kernel correctness, bounds, determinism.

The int kernel (``repro.kernels.sliding_scores_int``) must (a) agree
bitwise-closely with its pure-jnp quantized-operand oracle across shapes,
strides, D tilings and per-stream class tiles — in every mode: int8,
packed int4 wire codes, and the ±1 binary geometry, (b) track the float
path within quantization tolerance, (c) never overflow its int32
accumulators at the advertised bounds, and (d) be bitwise deterministic
across runs. The large-W VMEM working-set regression lives in
``test_workingset.py``; cross-backend / cross-precision *ranking*
contracts live in ``test_parity_matrix.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoding
from repro.kernels import ops
from repro.kernels import sliding_scores as k_ss
from repro.kernels import sliding_scores_int as k_int
from repro.sensing import adc

jax.config.update("jax_platform_name", "cpu")


def key(i):
    return jax.random.PRNGKey(i)


def make_inputs(seed, N, H, W, D, h, bits=8):
    frames = jax.random.uniform(key(seed), (N, H, W), maxval=1.5)
    codes = adc.pack_codes(adc.quantize_codes(frames, bits), bits)
    B0, b = encoding.make_perm_base_rows(key(seed + 1), h, D)
    C = jax.random.normal(key(seed + 2), (2, D))
    return frames, codes, B0, b, C


@pytest.mark.parametrize("stride", [1, 2, 4])
def test_int_kernel_matches_jnp_oracle(stride):
    """Pallas int kernel == pure-jnp int oracle (same quantized operands,
    same exact int32 accumulation; only float-epilogue rounding differs)."""
    N, H, W, D, h, w = 5, 18, 22, 64, 4, 5
    _, codes, B0, b, C = make_inputs(0, N, H, W, D, h)
    tiles = k_int.precompute_tiles_int(B0, b, C, W=W, w=w, stride=stride,
                                       block_d=32)
    got = k_int.fragment_scores_batch_int(codes, tiles, h=h, w=w,
                                          stride=stride, interpret=True)
    want = k_int.fragment_scores_batch_int_ref(codes, tiles, h=h, w=w,
                                               stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_int_path_tracks_float_path():
    """Same ADC capture through both datapaths: scores agree to the int8
    slab/class rounding (small vs the score dynamic range)."""
    N, H, W, D, h, w, stride = 6, 20, 24, 128, 4, 5, 2
    frames, codes, B0, b, C = make_inputs(10, N, H, W, D, h)
    ft = k_ss.precompute_tiles(B0, b, C, W=W, w=w, stride=stride,
                               block_d=64)
    fs = k_ss.fragment_scores_batch(adc.quantize(frames, 8), ft, h=h, w=w,
                                    stride=stride, interpret=True)
    it = k_int.precompute_tiles_int(B0, b, C, W=W, w=w, stride=stride,
                                    block_d=64)
    si = k_int.fragment_scores_batch_int(codes, it, h=h, w=w,
                                         stride=stride, interpret=True)
    span = float(jnp.max(fs) - jnp.min(fs))
    assert float(jnp.abs(si - fs).max()) < 0.05 * max(span, 0.1)


@pytest.mark.parametrize("H,W,h,w,stride", [
    (17, 23, 4, 5, 3),    # non-square; stride divides neither H-h nor W-w
    (19, 13, 6, 3, 4),    # W < H, single-column tail
    (15, 31, 5, 5, 7),    # wide frame, large stride -> tiny score map
])
def test_int_kernel_odd_shapes(H, W, h, w, stride):
    N, D = 3, 64
    _, codes, B0, b, C = make_inputs(20, N, H, W, D, h)
    tiles = k_int.precompute_tiles_int(B0, b, C, W=W, w=w, stride=stride,
                                       block_d=32)
    got = k_int.fragment_scores_batch_int(codes, tiles, h=h, w=w,
                                          stride=stride, interpret=True)
    my = (H - h) // stride + 1
    mx = (W - w) // stride + 1
    assert got.shape == (N, my, mx)
    want = k_int.fragment_scores_batch_int_ref(codes, tiles, h=h, w=w,
                                               stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("block_d", [1000, 48])
def test_int_kernel_non_divisible_block_d(block_d):
    """D % block_d != 0 collapses to a single D tile (and still matches)."""
    N, H, W, D, h, w, stride = 3, 14, 16, 96, 3, 4, 2
    _, codes, B0, b, C = make_inputs(30, N, H, W, D, h)
    tiles = k_int.precompute_tiles_int(B0, b, C, W=W, w=w, stride=stride,
                                       block_d=block_d)
    got = k_int.fragment_scores_batch_int(codes, tiles, h=h, w=w,
                                          stride=stride, interpret=True)
    want = k_int.fragment_scores_batch_int_ref(codes, tiles, h=h, w=w,
                                               stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_int_per_stream_tiles_one_launch():
    """(S, n_dt, mx, TD) int8 class tiles: batch element n reads stream
    n // C's classifier — matches scoring each stream separately."""
    S, C_, H, W, D, h, w, stride = 3, 4, 14, 18, 64, 3, 4, 2
    _, codes, B0, b, _ = make_inputs(40, S * C_, H, W, D, h)
    chvs = jax.random.normal(key(43), (S, 2, D))
    geom = k_int.precompute_geometry_int(B0, b, W=W, w=w, stride=stride,
                                         block_d=32)
    fleet_tiles = k_int.retile_classes_int_fleet(geom, chvs)
    got = k_int.fragment_scores_batch_int(codes, fleet_tiles, h=h, w=w,
                                          stride=stride, interpret=True,
                                          frames_per_stream=C_)
    per = codes.reshape(S, C_, H, W)
    for s in range(S):
        tiles_s = k_int.retile_classes_int(geom, chvs[s])
        want = k_int.fragment_scores_batch_int(per[s], tiles_s, h=h, w=w,
                                               stride=stride,
                                               interpret=True)
        np.testing.assert_allclose(np.asarray(got[s * C_:(s + 1) * C_]),
                                   np.asarray(want), rtol=1e-6, atol=1e-6)


def test_retile_matches_precompute_tiles_int():
    """precompute_tiles_int == retile_classes_int(precompute_geometry_int)
    bitwise — the online-learning install path can't drift from the
    offline one."""
    H, W, D, h, w, stride = 14, 16, 96, 3, 4, 2
    _, _, B0, b, C = make_inputs(50, 1, H, W, D, h)
    a = k_int.precompute_tiles_int(B0, b, C, W=W, w=w, stride=stride,
                                   block_d=48)
    geom = k_int.precompute_geometry_int(B0, b, W=W, w=w, stride=stride,
                                         block_d=48)
    c = k_int.retile_classes_int(geom, C)
    np.testing.assert_array_equal(np.asarray(a.cpos_t), np.asarray(c.cpos_t))
    np.testing.assert_array_equal(np.asarray(a.cneg_t), np.asarray(c.cneg_t))
    assert float(a.cpos_norm) == float(c.cpos_norm)


def test_window_norms_codes_exact_and_lsb_free():
    """The int32 SAT norm is exact: equals the int64 numpy ground truth,
    and (x LSB) equals the float path's window norms on reconstructions."""
    H, W, h, w, stride, bits = 20, 24, 5, 6, 2, 8
    frames = jax.random.uniform(key(60), (3, H, W), maxval=1.5)
    codes = adc.quantize_codes(frames, bits)
    got = k_int.window_norms_codes_batch(codes, h, w, stride)
    c = np.asarray(codes, np.int64)
    for i in range(3):
        my = (H - h) // stride + 1
        mx = (W - w) // stride + 1
        want = np.zeros((my, mx))
        for y in range(my):
            for x in range(mx):
                win = c[i, y * stride:y * stride + h,
                        x * stride:x * stride + w]
                want[y, x] = np.sqrt((win * win).sum())
        np.testing.assert_allclose(np.asarray(got[i]), want, rtol=1e-6)
    # LSB cancellation: float norms of the reconstruction = LSB * int norms
    fnorms = k_ss.window_norms_batch(adc.quantize(frames, bits), h, w,
                                     stride)
    np.testing.assert_allclose(np.asarray(fnorms),
                               np.asarray(got) * adc.lsb(bits),
                               rtol=1e-5, atol=1e-6)


def test_int_datapath_bounds_contract():
    b = ops.int_datapath_bounds(8, 128, 128, 16, 16)
    assert b["fits"]                       # the paper's scale is safe
    assert not ops.int_datapath_bounds(12, 512, 512, 16, 16)["fits"]
    with pytest.raises(ValueError):
        ops.assert_int_datapath_fits(12, 512, 512, 16, 16)
    ops.assert_int_datapath_fits(8, 128, 128, 16, 16)   # no raise


def test_int_kernel_worst_case_no_overflow():
    """All-max codes at max adc_bits: the int accumulators sit at their
    documented worst case and still match an exact int64 recomputation."""
    H, W, D, h, w, stride, bits = 12, 16, 32, 3, 4, 2, 8
    codes = jnp.full((1, H, W), (1 << bits) - 1, jnp.int32)
    B0, b_ = encoding.make_perm_base_rows(key(70), h, D)
    C = jax.random.normal(key(71), (2, D))
    tiles = k_int.precompute_tiles_int(B0, b_, C, W=W, w=w, stride=stride,
                                       block_d=D)
    got = k_int.fragment_scores_batch_int(codes, tiles, h=h, w=w,
                                          stride=stride, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    # int64 ground-truth accumulation of the projection for one fragment:
    # expand the window's shifted views from the padded base slabs (the
    # kernel rolls these out in-place; slabs_q[dt, r, i + j] is the value
    # the old pre-expanded layout stored at slab_mat[dt, r*W + i, j])
    base = np.asarray(tiles.geom.slabs_q, np.int64)[0]      # (h, D+W-1)
    slab = np.stack([base[:, i:i + D] for i in range(W)], axis=1)
    cmax = (1 << bits) - 1
    acc64 = slab[:, 0:w, :].sum(axis=(0, 1)) * cmax
    assert np.abs(acc64).max() <= ops.int_datapath_bounds(
        bits, H, W, h, w)["acc"]
    # the in-path int32 accumulation must equal the int64 one (no wrap)
    ref = k_int.fragment_scores_batch_int_ref(codes, tiles, h=h, w=w,
                                              stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("variant", ["int8", "int4-packed", "binary"])
def test_int_scores_bitwise_deterministic(variant):
    """Every accumulation order the int kernel ships — int8, the packed
    int4 unpack-then-accumulate, and the ±1 binary matmuls — is exact
    integer arithmetic in a fixed association, hence bitwise stable."""
    N, H, W, D, h, w, stride = 4, 16, 16, 64, 4, 4, 2
    bits = 4 if variant == "int4-packed" else 8
    _, codes, B0, b, C = make_inputs(80, N, H, W, D, h, bits=bits)
    mode = "binary" if variant == "binary" else "int8"
    tiles = k_int.precompute_tiles_int(B0, b, C, W=W, w=w, stride=stride,
                                       block_d=32, mode=mode)
    packed = variant == "int4-packed"
    if packed:
        codes = adc.pack_nibbles(codes)
    a = k_int.fragment_scores_batch_int(codes, tiles, h=h, w=w,
                                        stride=stride, interpret=True,
                                        packed=packed)
    b2 = k_int.fragment_scores_batch_int(codes, tiles, h=h, w=w,
                                         stride=stride, interpret=True,
                                         packed=packed)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b2))


def test_int4_packed_matches_unpacked_bitwise():
    """Nibble packing is pure wire format: the kernel's in-place unpack
    reproduces the unpacked-codes scores bit for bit, and both match the
    jnp oracle fed the same packed bytes."""
    N, H, W, D, h, w, stride = 4, 16, 18, 64, 4, 5, 2
    _, codes, B0, b, C = make_inputs(110, N, H, W, D, h, bits=4)
    tiles = k_int.precompute_tiles_int(B0, b, C, W=W, w=w, stride=stride,
                                       block_d=32)
    packed = adc.pack_nibbles(codes)
    got_u = k_int.fragment_scores_batch_int(codes, tiles, h=h, w=w,
                                            stride=stride, interpret=True)
    got_p = k_int.fragment_scores_batch_int(packed, tiles, h=h, w=w,
                                            stride=stride, interpret=True,
                                            packed=True)
    np.testing.assert_array_equal(np.asarray(got_u), np.asarray(got_p))
    ref_p = k_int.fragment_scores_batch_int_ref(packed, tiles, h=h, w=w,
                                                stride=stride, packed=True)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(ref_p),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("W", [128, 256])
def test_int_strided_kernel_matches_log_step_bitwise(W):
    """Unpacked codes in whole 128-column chunks take the strided
    alignment, packed ones the log-step: the int32 sums are exact, so
    the two kernels give the same scores bit for bit."""
    N, H, D, h, w, stride = 2, 10, 128, 4, 20, 12
    _, codes, B0, b, C = make_inputs(120, N, H, W, D, h, bits=4)
    assert k_ss.strided_alignment(W)
    assert not k_ss.strided_alignment(W, packed=True)
    tiles = k_int.precompute_tiles_int(B0, b, C, W=W, w=w, stride=stride,
                                       block_d=128)
    got = k_int.fragment_scores_batch_int(codes, tiles, h=h, w=w,
                                          stride=stride, interpret=True)
    log_step = k_int.fragment_scores_batch_int(
        adc.pack_nibbles(codes), tiles, h=h, w=w, stride=stride,
        interpret=True, packed=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(log_step))
    want = k_int.fragment_scores_batch_int_ref(codes, tiles, h=h, w=w,
                                               stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_binary_mode_kernel_matches_oracle():
    """mode="binary": slabs and class tiles really are ±1, the kernel
    still matches the quantized-operand oracle, and scores are finite."""
    N, H, W, D, h, w, stride = 4, 18, 22, 64, 4, 5, 2
    _, codes, B0, b, C = make_inputs(120, N, H, W, D, h)
    tiles = k_int.precompute_tiles_int(B0, b, C, W=W, w=w, stride=stride,
                                       block_d=32, mode="binary")
    assert set(np.unique(np.asarray(tiles.geom.slabs_q))) <= {-1, 1}
    assert set(np.unique(np.asarray(tiles.cpos_t))) <= {-1, 1}
    assert float(tiles.cpos_norm) == pytest.approx(np.sqrt(D))
    got = k_int.fragment_scores_batch_int(codes, tiles, h=h, w=w,
                                          stride=stride, interpret=True)
    want = k_int.fragment_scores_batch_int_ref(codes, tiles, h=h, w=w,
                                               stride=stride)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_pack_nibbles_needs_even_width_and_geometry_mode_guard():
    with pytest.raises(ValueError):
        adc.pack_nibbles(jnp.zeros((2, 4, 15), jnp.int32))
    B0, b_ = encoding.make_perm_base_rows(key(130), 3, 32)
    with pytest.raises(ValueError):
        k_int.precompute_geometry_int(B0, b_, W=14, w=3, stride=2,
                                      block_d=32, mode="ternary")


def test_int_kernel_rejects_float_frames():
    """The fused entry consumes codes; float frames are a usage bug."""
    frames, _, B0, b, C = make_inputs(90, 2, 14, 14, 32, 3)
    tiles = k_int.precompute_tiles_int(B0, b, C, W=14, w=3, stride=2,
                                       block_d=32)
    with pytest.raises(TypeError):
        k_int.fragment_scores_batch_int(frames, tiles, h=3, w=3, stride=2,
                                        interpret=True)
    with pytest.raises(TypeError):
        k_int.fragment_scores_batch_int_ref(frames, tiles, h=3, w=3,
                                            stride=2)


def test_ops_int_entry_points_route():
    """ops wrappers: batch entry == kernel; fleet entry == reshaped batch."""
    S, C_, H, W, D, h, w, stride = 2, 3, 14, 16, 64, 3, 4, 2
    _, codes, B0, b, C = make_inputs(100, S * C_, H, W, D, h)
    got_b = ops.fragment_score_map_batch_int(codes, C, B0, b, h=h, w=w,
                                             stride=stride, block_d=32)
    tiles = k_int.precompute_tiles_int(B0, b, C, W=W, w=w, stride=stride,
                                       block_d=32)
    want = k_int.fragment_scores_batch_int(codes, tiles, h=h, w=w,
                                           stride=stride, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want))
    got_f = ops.fragment_score_map_fleet_int(
        codes.reshape(S, C_, H, W), C, B0, b, h=h, w=w, stride=stride,
        block_d=32)
    assert got_f.shape == (S, C_) + want.shape[1:]
    np.testing.assert_array_equal(np.asarray(got_f).reshape(want.shape),
                                  np.asarray(want))
