"""Large-W VMEM working-set regression for the int kernel geometry.

The retired layout pre-expanded all W cyclic shifts into an
``(n_dt, h*W, TD)`` int8 operand — linear in W, overrunning VMEM exactly
at deployment scale (h=16, W=4096, TD=512 -> 32 MB/tile). The
rolling-shift layout keeps only the padded base slabs plus a bounded
chunk scratch: O(window) in W. This file pins that asymmetry the way the
issue demands: the OLD layout's byte count asserted *over* the budget at
large W, the NEW one under it — so a future "optimization" that
re-materializes shifts cannot land silently.
"""

import jax
import numpy as np
import pytest

from repro.core import encoding
from repro.kernels import ops
from repro.kernels import sliding_scores_int as k_int
from repro.sensing import adc

jax.config.update("jax_platform_name", "cpu")

#: the deployment-scale config the old layout failed at (4-bit codes so
#: the sum-of-squares accumulator stays exact and the VMEM bound is the
#: only thing under test)
LARGE_W = dict(adc_bits=4, H=128, W=4096, h=16, w=16, stride=16,
               block_d=512)


def test_expanded_layout_over_budget_new_layout_under():
    b = k_int.int_datapath_bounds(**LARGE_W)
    # the regression proof: same config, old layout busts the budget...
    assert b["vmem_expanded_bytes"] > b["vmem_limit_bytes"]
    # ...while the rolling-shift working set fits with >2x headroom
    assert b["vmem_bytes"] <= b["vmem_limit_bytes"] // 2
    assert b["fits"]
    # and the guard accepts the config the old layout would have died on
    ops.assert_int_datapath_fits(**LARGE_W)


def test_working_set_is_o_window_in_w():
    """Doubling W doubles the expanded operand but only adds halo/mask
    bytes to the rolling-shift working set."""
    base = dict(LARGE_W)
    b1 = k_int.int_datapath_bounds(**base)
    base["W"] *= 2
    b2 = k_int.int_datapath_bounds(**base)
    exp_growth = b2["vmem_expanded_bytes"] - b1["vmem_expanded_bytes"]
    new_growth = b2["vmem_bytes"] - b1["vmem_bytes"]
    # the expanded operand alone grows by h * dW * td bytes
    assert exp_growth >= LARGE_W["h"] * LARGE_W["W"] * LARGE_W["block_d"]
    # the rolling layout only adds the terms both layouts share (codes
    # block, window mask, bias/class/acc tiles) plus W-1 halo columns —
    # its slab term grows by h * dW bytes, vs h * dW * td expanded
    shared_growth = new_growth - LARGE_W["h"] * LARGE_W["W"]  # minus halo
    assert (exp_growth - shared_growth
            >= LARGE_W["h"] * LARGE_W["W"] * LARGE_W["block_d"])
    assert new_growth < exp_growth / 5


def test_geometry_stores_no_expanded_operand():
    """IntScoreGeometry holds padded base slabs, not an (n_dt, h*W, TD)
    slab matrix — asserted structurally, not just via the byte model."""
    h, W, w, stride, D, td = 4, 96, 5, 3, 128, 32
    B0, b = encoding.make_perm_base_rows(jax.random.PRNGKey(0), h, D)
    geom = k_int.precompute_geometry_int(B0, b, W=W, w=w, stride=stride,
                                         block_d=td)
    assert not hasattr(geom, "slab_mat")
    n_dt = D // td
    # td + W columns rounded up to a lane multiple (the strided alignment
    # reads lane-aligned products)
    assert geom.slabs_q.shape == (n_dt, h, 128)
    # per D-tile slab bytes: h * slab_width, nowhere near h * W * td
    assert geom.slabs_q[0].size < h * W * td / 8


def test_oversized_new_layout_still_raises():
    """The bound is two-sided: a genuinely oversized (window, tile)
    config trips the VMEM branch of assert_int_datapath_fits too."""
    with pytest.raises(ValueError, match="working set"):
        ops.assert_int_datapath_fits(4, 64, 4096, 16, 16, stride=1,
                                     block_d=4096)


def test_large_w_kernel_matches_oracle():
    """4x the benchmark's default frame width, W past the roll-chunk
    boundary: the chunked rolling-shift kernel still matches the jnp
    quantized-operand oracle (and its geometry passes the VMEM guard)."""
    N, H, W, D, h, w, stride, bits = 2, 12, 144, 256, 4, 5, 4, 8
    frames = jax.random.uniform(jax.random.PRNGKey(1), (N, H, W),
                                maxval=1.5)
    codes = adc.pack_codes(adc.quantize_codes(frames, bits), bits)
    B0, b = encoding.make_perm_base_rows(jax.random.PRNGKey(2), h, D)
    C = jax.random.normal(jax.random.PRNGKey(3), (2, D))
    ops.assert_int_datapath_fits(bits, H, W, h, w, stride=stride,
                                 block_d=64)
    tiles = k_int.precompute_tiles_int(B0, b, C, W=W, w=w, stride=stride,
                                       block_d=64)
    got = k_int.fragment_scores_batch_int(codes, tiles, h=h, w=w,
                                          stride=stride, interpret=True)
    want = k_int.fragment_scores_batch_int_ref(codes, tiles, h=h, w=w,
                                               stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_d16384_fits_only_when_sharded_over_hyperdim():
    """The hyperdim axis's reason to exist, certified by the byte model:
    at D=16384 one device would hold the whole hypervector per grid step
    (block_d = D) and busts the VMEM budget, while an 8-way D-shard's
    2048-wide tile fits (64x64 frames, 8x8 fragments, stride 8, 8-bit
    codes)."""
    cfg = dict(adc_bits=8, H=64, W=64, h=8, w=8, stride=8)
    single = k_int.int_datapath_bounds(**cfg, block_d=16384)
    shard = k_int.int_datapath_bounds(**cfg, block_d=16384 // 8)
    assert not single["fits"]
    assert single["vmem_bytes"] > single["vmem_limit_bytes"]
    assert shard["fits"]
    assert shard["vmem_bytes"] <= shard["vmem_limit_bytes"]
