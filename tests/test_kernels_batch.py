"""Batched sliding-scores kernel: parity vs per-frame and pure-jnp paths.

The batched kernel (grid ``(N, n_dt)``) must agree with (a) the
per-frame kernel it generalizes, and (b) the pure-jnp
``fragment_score_map`` oracle — across dtypes, strides, and non-divisible
``D % block_d``. Plus edge cases of ``frame_detection_score``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import encoding, hypersense
from repro.kernels import ops
from repro.kernels import ref
from repro.kernels import sliding_scores as k_ss

jax.config.update("jax_platform_name", "cpu")


def key(i):
    return jax.random.PRNGKey(i)


TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("stride", [1, 2, 4])
def test_batch_matches_per_frame_and_jnp(stride, dtype):
    N, H, W, D, h, w = 5, 18, 22, 64, 4, 5
    frames = jax.random.uniform(key(0), (N, H, W), dtype=jnp.float32)
    frames = frames.astype(dtype)
    B0, b = encoding.make_perm_base_rows(key(1), h, D)
    C = jax.random.normal(key(2), (2, D))
    tiles = k_ss.precompute_tiles(B0, b, C, W=W, w=w, stride=stride,
                                  block_d=32)
    got = k_ss.fragment_scores_batch(frames, tiles, h=h, w=w, stride=stride,
                                     interpret=True)
    assert got.shape[0] == N
    for i in range(N):
        per_frame = k_ss.fragment_scores(frames[i], tiles, h=h, w=w,
                                         stride=stride, interpret=True)
        np.testing.assert_allclose(np.asarray(got[i]),
                                   np.asarray(per_frame),
                                   rtol=1e-6, atol=1e-6)
        want = hypersense.fragment_score_map(
            frames[i].astype(jnp.float32), C, B0, b, h=h, w=w,
            stride=stride, backend="jnp")
        np.testing.assert_allclose(np.asarray(got[i], np.float32),
                                   np.asarray(want, np.float32),
                                   **TOL[dtype])


@pytest.mark.parametrize("block_d", [1000, 48])
def test_batch_non_divisible_block_d(block_d):
    """D % block_d != 0 collapses to a single D tile (and still matches)."""
    N, H, W, D, h, w, stride = 3, 14, 16, 96, 3, 4, 2
    frames = jax.random.uniform(key(3), (N, H, W))
    B0, b = encoding.make_perm_base_rows(key(4), h, D)
    C = jax.random.normal(key(5), (2, D))
    tiles = k_ss.precompute_tiles(B0, b, C, W=W, w=w, stride=stride,
                                  block_d=block_d)
    got = k_ss.fragment_scores_batch(frames, tiles, h=h, w=w, stride=stride,
                                     interpret=True)
    for i in range(N):
        want = ref.fragment_scores(frames[i], C, B0, b, h=h, w=w,
                                   stride=stride)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("nonlin", ["rff", "linear"])
def test_batch_nonlinearities(nonlin):
    N, H, W, D, h, w = 2, 12, 16, 96, 3, 4
    frames = jax.random.uniform(key(6), (N, H, W))
    B0, b = encoding.make_perm_base_rows(key(7), h, D)
    C = jax.random.normal(key(8), (2, D))
    tiles = k_ss.precompute_tiles(B0, b, C, W=W, w=w, stride=1, block_d=48)
    got = k_ss.fragment_scores_batch(frames, tiles, h=h, w=w, stride=1,
                                     nonlinearity=nonlin, interpret=True)
    for i in range(N):
        want = ref.fragment_scores(frames[i], C, B0, b, h=h, w=w, stride=1,
                                   nonlinearity=nonlin)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("H,W,h,w,stride", [
    (17, 23, 4, 5, 3),    # non-square; stride divides neither H-h nor W-w
    (19, 13, 6, 3, 4),    # W < H, W-w not divisible, single-column tail
    (15, 31, 5, 5, 7),    # wide frame, large stride -> tiny score map
])
def test_batch_odd_shapes_match_jnp(H, W, h, w, stride):
    """Non-square frames and strides that don't divide ``H - h``/``W - w``:
    the floor'd (my, mx) grid must agree with the jnp oracle everywhere."""
    N, D = 3, 64
    my = (H - h) // stride + 1
    mx = (W - w) // stride + 1
    assert (H - h) % stride != 0 or (W - w) % stride != 0
    frames = jax.random.uniform(key(20), (N, H, W))
    B0, b = encoding.make_perm_base_rows(key(21), h, D)
    C = jax.random.normal(key(22), (2, D))
    tiles = k_ss.precompute_tiles(B0, b, C, W=W, w=w, stride=stride,
                                  block_d=32)
    got = k_ss.fragment_scores_batch(frames, tiles, h=h, w=w, stride=stride,
                                     interpret=True)
    assert got.shape == (N, my, mx)
    for i in range(N):
        want = hypersense.fragment_score_map(frames[i], C, B0, b, h=h, w=w,
                                             stride=stride, backend="jnp")
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("W,td", [
    (128, 512),    # the paper's band chunk: (128, 640) products
    (256, 512),    # two chunks: the second reads slab columns [128, 768)
])
def test_strided_aligner_bitwise_equals_log_step(W, td):
    """In a Pallas kernel (interpret mode): one strided lane rotate of
    the row-reversed product gives, row for row, exactly the bits of the
    log-step roll-and-select of the product itself."""
    h, cw = 8, k_ss.W_CHUNK
    L = -(-(td + cw) // 128) * 128
    n = W // cw
    band = jax.random.normal(key(30), (h, W))
    slabs = jax.random.normal(key(31), (h, k_ss.slab_width(td, W)))

    def kern(band_ref, slab_ref, want_ref, got_ref):
        for k in range(n):
            c0 = k * cw
            p = k_ss._project(band_ref[:, c0:c0 + cw],
                              slab_ref[:, c0:c0 + L], planes=0)
            shift = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
            want_ref[k] = k_ss._rows_to_diagonals(p, shift,
                                                  max_shift=cw - 1, td=td)
            got_ref[k] = k_ss._rotate_to_diagonals(p[::-1], td=td)[::-1]

    out = jax.ShapeDtypeStruct((n, cw, td), jnp.float32)
    want, got = pl.pallas_call(kern, out_shape=[out, out],
                               interpret=True)(band, slabs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    p = np.asarray(band[:, :cw]).T.astype(np.float64) @ np.asarray(
        slabs[:, :L], np.float64)
    i, j = np.ogrid[:cw, :td]
    np.testing.assert_allclose(np.asarray(want[0]), p[i, i + j],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("W", [128, 256])
def test_batch_strided_path_matches_jnp(W):
    """Frames in whole 128-column chunks take the strided alignment
    (columns reversed per chunk before the launch): the scores still
    match the jnp oracle, across a chunk boundary too."""
    N, H, D, h, w, stride = 2, 10, 256, 4, 24, 20
    assert k_ss.strided_alignment(W)
    frames = jax.random.uniform(key(32), (N, H, W))
    B0, b = encoding.make_perm_base_rows(key(33), h, D)
    C = jax.random.normal(key(34), (2, D))
    tiles = k_ss.precompute_tiles(B0, b, C, W=W, w=w, stride=stride,
                                  block_d=128)
    got = k_ss.fragment_scores_batch(frames, tiles, h=h, w=w, stride=stride,
                                     interpret=True)
    for i in range(N):
        want = hypersense.fragment_score_map(frames[i], C, B0, b, h=h, w=w,
                                             stride=stride, backend="jnp")
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_fleet_reshape_plumbing_matches_batch():
    """(S, C, H, W) fleet entry point == reshaped batch entry point."""
    S, C, H, W, D, h, w, stride = 3, 4, 14, 18, 64, 3, 4, 2
    frames = jax.random.uniform(key(23), (S, C, H, W))
    B0, b = encoding.make_perm_base_rows(key(24), h, D)
    Chv = jax.random.normal(key(25), (2, D))
    got = ops.fragment_score_map_fleet(frames, Chv, B0, b, h=h, w=w,
                                       stride=stride)
    want = ops.fragment_score_map_batch(frames.reshape(S * C, H, W), Chv,
                                        B0, b, h=h, w=w, stride=stride)
    assert got.shape == (S, C) + want.shape[1:]
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want).reshape(got.shape))


def test_batch_of_one_equals_single():
    H, W, D, h, w, stride = 14, 14, 64, 3, 3, 1
    frame = jax.random.uniform(key(9), (H, W))
    B0, b = encoding.make_perm_base_rows(key(10), h, D)
    C = jax.random.normal(key(11), (2, D))
    tiles = k_ss.precompute_tiles(B0, b, C, W=W, w=w, stride=stride)
    batched = k_ss.fragment_scores_batch(frame[None], tiles, h=h, w=w,
                                         stride=stride, interpret=True)
    single = k_ss.fragment_scores(frame, tiles, h=h, w=w, stride=stride,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(batched[0]),
                                  np.asarray(single))


def test_window_norms_batch_matches_per_frame():
    frames = jax.random.normal(key(12), (4, 20, 24))
    got = k_ss.window_norms_batch(frames, 5, 6, 2)
    for i in range(4):
        want = k_ss.window_norms(frames[i], 5, 6, 2)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def test_ops_fragment_score_map_batch_matches_jnp():
    N, H, W, D, h, w, stride = 4, 14, 14, 64, 3, 3, 1
    frames = jax.random.uniform(key(13), (N, H, W))
    B0, b = encoding.make_perm_base_rows(key(14), h, D)
    C = jax.random.normal(key(15), (2, D))
    got = ops.fragment_score_map_batch(frames, C, B0, b, h=h, w=w,
                                       stride=stride)
    for i in range(N):
        want = hypersense.fragment_score_map(frames[i], C, B0, b, h=h, w=w,
                                             stride=stride, backend="jnp")
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


# (runner-level pallas==jnp parity lives in the backend x precision x
# adapt matrix: tests/test_parity_matrix.py. frame_scores_batch itself —
# the public batch-scoring API with its own precision/sequential routing —
# is pinned here across its full routing grid.)

@pytest.mark.parametrize("precision", ["float32", "int8"])
@pytest.mark.parametrize("sequential", [False, True])
def test_frame_scores_batch_routing_grid(precision, sequential):
    """Every (backend, precision, sequential) route returns the same frame
    scores: pallas==jnp per configuration, sequential==batched per
    configuration (int8 within exact-path tolerance, float32 vs its own
    batch exactly)."""
    N, H, W, D, h, w, stride = 6, 14, 14, 64, 3, 3, 2
    frames = jax.random.uniform(key(16), (N, H, W), maxval=1.5)
    B0, b = encoding.make_perm_base_rows(key(17), h, D)
    C = jax.random.normal(key(18), (2, D))
    model = hypersense.HyperSenseModel(C, B0, b, h, w, stride,
                                       t_score=0.0, t_detection=2)
    kw = dict(precision=precision, sequential=sequential)
    if precision == "int8":
        kw["adc_bits"] = 8
    got_p = hypersense.frame_scores_batch(model, frames, backend="pallas",
                                          **kw)
    got_j = hypersense.frame_scores_batch(model, frames, backend="jnp",
                                          **kw)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(got_j),
                               rtol=2e-4, atol=2e-4)
    # sequential is a memory strategy, not a numerics change
    ref = hypersense.frame_scores_batch(
        model, frames, backend="jnp",
        **{**kw, "sequential": False})
    np.testing.assert_allclose(np.asarray(got_j), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# frame_detection_score edge cases
# ---------------------------------------------------------------------------

def test_frame_detection_score_t_at_least_num_fragments_clamps():
    """t_detection >= #fragments clamps to the minimum (ROC stays defined)."""
    scores = jnp.asarray([[3.0, 1.0], [2.0, 4.0]])
    for td in (4, 5, 100):
        got = hypersense.frame_detection_score(scores, td)
        assert float(got) == 1.0  # smallest fragment score


def test_frame_detection_score_all_equal():
    scores = jnp.full((3, 3), 0.25)
    for td in (0, 4, 8, 20):
        assert float(hypersense.frame_detection_score(scores, td)) == 0.25


def test_frame_detection_score_order_statistic():
    scores = jnp.asarray([[0.75, -0.5], [0.125, 0.25]])
    assert float(hypersense.frame_detection_score(scores, 0)) == 0.75
    assert float(hypersense.frame_detection_score(scores, 1)) == 0.25
    assert float(hypersense.frame_detection_score(scores, 3)) == -0.5
