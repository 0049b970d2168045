"""Integer ADC-code datapaths: throughput, working set, accuracy, determinism.

The claims behind the low-precision integer datapaths:

* ``throughput`` — the fused encode->score int kernel
  (:mod:`repro.kernels.sliding_scores_int`: rolling in-kernel shifts over
  the padded base slabs, one window matmul per grid step) processes a
  chunk at least as fast as the float kernel at chunk sizes >= 8, AND at
  least as fast as the *retired expanded-slab layout* (reconstructed
  locally here as a baseline twin: the ``(h*W, TD)`` pre-shifted slab
  whose VMEM footprint grew linearly in W). On CPU all paths run in
  Pallas interpret mode, so the ratios — not the absolute fps — are the
  claim; on TPU the int paths additionally ride the int8 MXU and the
  4x (int8) / 8x (packed int4) smaller operand traffic.
* ``working set`` — at W four times the benchmark frame the rolling
  kernel still matches its jnp oracle and
  ``assert_int_datapath_fits`` admits the geometry; the byte model pins
  that the same config's *expanded* layout would not have fit.
* ``auc parity`` — integer rounding of slabs/class tiles costs
  essentially no detection quality: frame-score AUC on the synthetic
  stream AND on a drifted stream is within ``AUC_TOL`` of the float
  path fed the same ADC capture, for ``int8`` (8-bit codes) and packed
  ``int4`` (4-bit codes vs float at 4 bits).
* ``binary curve`` — the bipolar +-1 gate is a *reduced-D operating
  point*: its D-vs-AUC tradeoff is reported (not gated point-by-point —
  sign-quantizing both slabs and class HVs degrades with growing D as
  the class prototypes' disagreement margin thins), with a sanity gate
  on the best point of the curve.
* ``determinism`` — integer accumulation is associative: the int path is
  bitwise identical across *separate compilations* of the kernel
  (``jax.clear_caches()`` between runs, so this is not a cached-executable
  tautology; cross-process reproducibility follows from the same
  property).

Run:  PYTHONPATH=src python benchmarks/int_datapath.py [--check]
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas.tpu import CompilerParams

from repro.core import fragment_model as fm, hypersense, metrics
from repro.core.encoding import apply_nonlinearity, make_perm_base_rows
from repro.kernels import ops
from repro.kernels import sliding_scores as k_ss
from repro.kernels import sliding_scores_int as k_int
from repro.sensing import adc, fragments, synthetic

# CPU-tractable scale (interpret mode); chunk >= 8 is the claimed regime.
FRAME = 32
FRAG = 8
STRIDE = 4
DIM = 256
BLOCK_D = 128
CHUNK = 16
BITS = 8

# the AUC scenario uses a *trained* gate so scores are meaningful
AUC_DIM = 512
N_STREAM = 160
AUC_TOL = 0.01

# binary is evaluated as a curve over model dimensionality; the sanity
# gate is on the best point (small D — see the module docstring)
BINARY_DIMS = (128, 256, 512)
BINARY_MIN_BEST_AUC = 0.85

# the large-W regression check: 4x the benchmark frame width. D must
# cover the slab halo (td + W - 1 <= D), hence the dedicated dims.
LARGE_W = 4 * FRAME
LARGE_W_DIM = 256
LARGE_W_BLOCK_D = 128


def _time(fn, reps: int) -> float:
    fn()  # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------------------
# Expanded-slab baseline twin (the RETIRED layout, kept only as a yardstick)
# ---------------------------------------------------------------------------

def _expanded_kernel(codes_ref, slab_ref, mask_ref, bias_ref, cpos_ref,
                     cneg_ref, norm_ref, dpos_ref, dneg_ref, qq_ref, *,
                     h: int, stride: int, w: int, W: int, mx: int,
                     td: int, nonlinearity: str):
    """The pre-rolling-shift kernel body: consumes the ``(h*W, TD)``
    expanded shifted slab the old layout materialized in HBM and pulled
    whole into VMEM. Epilogue identical to the live kernel — only the
    projection core differs, which is exactly what the race measures."""
    ky = pl.program_id(1)
    block = codes_ref[0, pl.ds(ky * stride, h), :]
    slab3 = slab_ref[0].reshape(h, W, td)
    codes = block.astype(jnp.int32)
    g = codes[0][:, None] * slab3[0].astype(jnp.int32)
    for r in range(1, h):
        g = g + codes[r][:, None] * slab3[r].astype(jnp.int32)
    acc = jax.lax.dot_general(
        mask_ref[...].astype(jnp.int32), g, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    norms = norm_ref[0].astype(jnp.float32)
    s_n = acc.astype(jnp.float32) / norms[0][:, None]
    phi = apply_nonlinearity(s_n, bias_ref[0], nonlinearity)
    dpos = jnp.sum(phi * cpos_ref[0].astype(jnp.float32),
                   axis=1)[None, None, :]
    dneg = jnp.sum(phi * cneg_ref[0].astype(jnp.float32),
                   axis=1)[None, None, :]
    qq = jnp.sum(phi * phi, axis=1)[None, None, :]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dpos_ref[...] = jnp.zeros_like(dpos_ref)
        dneg_ref[...] = jnp.zeros_like(dneg_ref)
        qq_ref[...] = jnp.zeros_like(qq_ref)

    dpos_ref[...] += dpos
    dneg_ref[...] += dneg
    qq_ref[...] += qq


def _expand_slabs(geom: k_int.IntScoreGeometry, W: int) -> jnp.ndarray:
    """Re-materialize the retired ``(n_dt, h*W, TD)`` operand from the
    compact padded base slabs (bit-identical: the old layout quantized
    before expanding, so slices of ``slabs_q`` ARE its rows)."""
    n_dt, h, _ = geom.slabs_q.shape
    td = geom.block_d
    rows = jnp.stack([geom.slabs_q[:, :, i:i + td] for i in range(W)],
                     axis=2)                       # (n_dt, h, W, td)
    return rows.reshape(n_dt, h * W, td)


@functools.partial(jax.jit, static_argnames=("h", "w", "stride"))
def _expanded_scores(codes, slab_mat, tiles, *, h: int, w: int,
                     stride: int):
    """Batch wrapper for the baseline twin (single-model tiles only)."""
    N, H, W = codes.shape
    my = (H - h) // stride + 1
    mx = (W - w) // stride + 1
    geom = tiles.geom
    n_dt = slab_mat.shape[0]
    td = geom.block_d
    norms = k_int.window_norms_codes_batch(codes, h, w, stride)
    norms = jnp.maximum(norms, 1e-8) / geom.slab_scale
    # win_mask[kx, i] = [kx*stride <= i < kx*stride + w]
    i = jnp.arange(W)[None, :]
    kx = jnp.arange(mx)[:, None] * stride
    win_mask = ((i >= kx) & (i < kx + w)).astype(jnp.int8)    # (mx, W)
    kern = functools.partial(_expanded_kernel, h=h, stride=stride, w=w,
                             W=W, mx=mx, td=td, nonlinearity="rff")
    dpos, dneg, qq = pl.pallas_call(
        kern,
        grid=(N, my, n_dt),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda n, i, j: (n, 0, 0)),
            pl.BlockSpec((1, h * W, td), lambda n, i, j: (j, 0, 0)),
            pl.BlockSpec((mx, W), lambda n, i, j: (0, 0)),
            pl.BlockSpec((1, mx, td), lambda n, i, j: (j, 0, 0)),
            pl.BlockSpec((1, mx, td), lambda n, i, j: (j, 0, 0)),
            pl.BlockSpec((1, mx, td), lambda n, i, j: (j, 0, 0)),
            pl.BlockSpec((1, 1, mx), lambda n, i, j: (n, i, 0)),
        ],
        out_specs=[pl.BlockSpec((1, 1, mx), lambda n, i, j: (n, i, 0))] * 3,
        out_shape=[jax.ShapeDtypeStruct((N, my, mx), jnp.float32)] * 3,
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=True,
    )(codes, slab_mat, win_mask, geom.bias_t, tiles.cpos_t,
      tiles.cneg_t, norms)
    return k_ss._cosine_epilogue(dpos, dneg, qq, tiles, False, 0)


def throughput(n_frames: int = CHUNK, reps: int = 8) -> dict:
    """Chunk throughput: float kernel vs rolling-shift int8 kernel vs the
    retired expanded-slab baseline, same model, same ADC capture."""
    B0, b = make_perm_base_rows(jax.random.PRNGKey(0), FRAG, DIM)
    chvs = jax.random.normal(jax.random.PRNGKey(1), (2, DIM))
    frames = jax.random.uniform(jax.random.PRNGKey(2),
                                (n_frames, FRAME, FRAME), maxval=1.5)
    # both paths see the SAME ADC capture: float gets the reconstruction,
    # int gets the raw codes
    codes = adc.pack_codes(adc.quantize_codes(frames, BITS), BITS)
    recon = adc.quantize(frames, BITS)
    ftiles = ops.precompute_tiles(B0, b, chvs, W=FRAME, w=FRAG,
                                  stride=STRIDE, block_d=BLOCK_D)
    itiles = ops.precompute_tiles_int(B0, b, chvs, W=FRAME, w=FRAG,
                                      stride=STRIDE, block_d=BLOCK_D)
    slab_mat = jax.block_until_ready(_expand_slabs(itiles.geom, FRAME))

    t_f = _time(lambda: jax.block_until_ready(
        ops.fragment_score_map_batch(recon, chvs, B0, b, h=FRAG, w=FRAG,
                                     stride=STRIDE, tiles=ftiles)), reps)
    t_i = _time(lambda: jax.block_until_ready(
        ops.fragment_score_map_batch_int(codes, chvs, B0, b, h=FRAG,
                                         w=FRAG, stride=STRIDE,
                                         tiles=itiles)), reps)
    t_e = _time(lambda: jax.block_until_ready(
        _expanded_scores(codes, slab_mat, itiles, h=FRAG, w=FRAG,
                         stride=STRIDE)), reps)
    # the race is only fair if both kernels compute the same thing
    s_new = np.asarray(ops.fragment_score_map_batch_int(
        codes, chvs, B0, b, h=FRAG, w=FRAG, stride=STRIDE, tiles=itiles))
    s_exp = np.asarray(_expanded_scores(codes, slab_mat, itiles, h=FRAG,
                                        w=FRAG, stride=STRIDE))
    np.testing.assert_allclose(s_new, s_exp, rtol=1e-6, atol=1e-6)
    return {"float_fps": n_frames / t_f, "int8_fps": n_frames / t_i,
            "expanded_fps": n_frames / t_e, "speedup": t_f / t_i,
            "speedup_vs_expanded": t_e / t_i, "chunk": n_frames}


# ---------------------------------------------------------------------------
# Large-W working set
# ---------------------------------------------------------------------------

def large_w_check() -> dict:
    """W = 4x the benchmark frame: the rolling kernel matches its jnp
    oracle (exact integer core, tolerance-level float epilogue) where
    the retired layout's byte model says it would not have fit a
    deployment-scale VMEM working set."""
    H, W = FRAME, LARGE_W
    D, td = LARGE_W_DIM, LARGE_W_BLOCK_D
    ops.assert_int_datapath_fits(BITS, H, W, FRAG, FRAG, stride=STRIDE,
                                 block_d=td)
    # the deployment-scale asymmetry the rewrite exists for: rolling fits,
    # expanded does not (16x16 windows over W=4096 at 4-bit codes)
    bounds = k_int.int_datapath_bounds(4, 128, 4096, 16, 16, stride=16,
                                       block_d=512)
    assert bounds["fits"], "rolling layout must admit deployment scale"
    assert bounds["vmem_expanded_bytes"] > bounds["vmem_limit_bytes"], (
        "byte model lost the expanded-layout regression")

    B0, b = make_perm_base_rows(jax.random.PRNGKey(5), FRAG, D)
    chvs = jax.random.normal(jax.random.PRNGKey(6), (2, D))
    frames = jax.random.uniform(jax.random.PRNGKey(7), (4, H, W),
                                maxval=1.5)
    codes = adc.pack_codes(adc.quantize_codes(frames, BITS), BITS)
    tiles = k_int.precompute_tiles_int(B0, b, chvs, W=W, w=FRAG,
                                       stride=STRIDE, block_d=td)
    got = np.asarray(k_int.fragment_scores_batch_int(
        codes, tiles, h=FRAG, w=FRAG, stride=STRIDE, interpret=True))
    want = np.asarray(k_int.fragment_scores_batch_int_ref(
        codes, tiles, h=FRAG, w=FRAG, stride=STRIDE))
    return {"W": W, "oracle_max_err": float(np.abs(got - want).max()),
            "guard_ok": True,
            "expanded_would_fit": bool(
                bounds["vmem_expanded_bytes"] <= bounds["vmem_limit_bytes"])}


# ---------------------------------------------------------------------------
# Accuracy
# ---------------------------------------------------------------------------

def _train_gate(cfg, dim: int):
    """Fragment model trained on the clean distribution (as adaptation.py)."""
    frames, masks, _ = synthetic.make_dataset(jax.random.PRNGKey(0), 60,
                                              cfg)
    frs, labs = fragments.sample_fragments(
        np.asarray(frames), np.asarray(masks), h=FRAG, w=FRAG,
        per_frame=2, seed=0)
    model, _ = fm.train_fragment_model(
        jax.random.PRNGKey(1), jnp.asarray(frs), jnp.asarray(labs),
        dim=dim, epochs=8)
    B0 = model.B.reshape(FRAG, FRAG, -1)[:, 0, :]
    return hypersense.from_fragment_model(model, B0, h=FRAG, w=FRAG,
                                          stride=STRIDE, t_detection=1)


def _auc(scores, labels) -> float:
    fpr, tpr, _ = metrics.roc_curve(np.asarray(scores), np.asarray(labels))
    return float(metrics.auc(fpr, tpr))


def auc_parity(backend: str = "pallas") -> dict:
    """Frame-score AUC: float vs int8 (8-bit codes) and float-at-4-bits
    vs packed int4, on synthetic + drift. Each integer path is compared
    against the float path fed the SAME ADC capture depth, so the gap
    isolates the datapath, not the converter."""
    cfg = synthetic.RadarConfig(height=FRAME, width=FRAME)
    hs = _train_gate(cfg, AUC_DIM)
    drift = synthetic.DriftConfig(background_gain=(0.0, 0.5),
                                  noise_sigma=(0.12, 0.25),
                                  object_intensity=(0.8, 0.45))
    scenarios = {
        "synthetic": synthetic.make_stream(
            jax.random.PRNGKey(3), N_STREAM, cfg, event_prob=0.08,
            event_len=10),
        "drift": synthetic.make_drift_stream(
            jax.random.PRNGKey(4), N_STREAM, cfg, drift, event_prob=0.08,
            event_len=10),
    }
    out = {"backend": backend}
    for name, (frames, labels) in scenarios.items():
        s_f = hypersense.frame_scores_batch(
            hs, adc.quantize(frames, BITS), backend=backend)
        s_i = hypersense.frame_scores_batch(hs, frames, backend=backend,
                                            precision="int8",
                                            adc_bits=BITS)
        s_f4 = hypersense.frame_scores_batch(
            hs, adc.quantize(frames, 4), backend=backend)
        s_i4 = hypersense.frame_scores_batch(hs, frames, backend=backend,
                                             precision="int4", adc_bits=4)
        out[f"{name}_float_auc"] = _auc(s_f, labels)
        out[f"{name}_int8_auc"] = _auc(s_i, labels)
        out[f"{name}_gap"] = abs(out[f"{name}_float_auc"]
                                 - out[f"{name}_int8_auc"])
        out[f"{name}_int4_auc"] = _auc(s_i4, labels)
        out[f"{name}_int4_gap"] = abs(_auc(s_f4, labels)
                                      - out[f"{name}_int4_auc"])
    return out


def binary_curve(backend: str = "pallas") -> dict:
    """The binary gate's D-vs-AUC tradeoff on the synthetic stream.

    Reported as a curve because it is NOT monotone-up in D: the float
    gate saturates while double sign-quantization (slabs AND class HVs)
    erodes the class prototypes' disagreement margin as D grows — the
    binary gate is a reduced-D operating point, and the sanity gate
    anchors on the best point of the curve accordingly.
    """
    cfg = synthetic.RadarConfig(height=FRAME, width=FRAME)
    frames, labels = synthetic.make_stream(
        jax.random.PRNGKey(3), N_STREAM, cfg, event_prob=0.08,
        event_len=10)
    out = {"backend": backend}
    best = 0.0
    for dim in BINARY_DIMS:
        hs = _train_gate(cfg, dim)
        s_f = hypersense.frame_scores_batch(
            hs, adc.quantize(frames, BITS), backend=backend)
        s_b = hypersense.frame_scores_batch(hs, frames, backend=backend,
                                            precision="binary",
                                            adc_bits=BITS)
        out[f"d{dim}_float_auc"] = _auc(s_f, labels)
        out[f"d{dim}_binary_auc"] = _auc(s_b, labels)
        best = max(best, out[f"d{dim}_binary_auc"])
    out["best_binary_auc"] = best
    return out


def determinism() -> dict:
    """Int-path runs must be bitwise identical across fresh compilations.

    ``jax.clear_caches()`` between the two runs discards the compiled
    executable, so the comparison spans two independent compiles — a
    scheduling- or layout-dependent reduction would be free to differ.
    """
    B0, b = make_perm_base_rows(jax.random.PRNGKey(7), FRAG, DIM)
    chvs = jax.random.normal(jax.random.PRNGKey(8), (2, DIM))
    frames = jax.random.uniform(jax.random.PRNGKey(9),
                                (CHUNK, FRAME, FRAME), maxval=1.5)
    codes = adc.pack_codes(adc.quantize_codes(frames, BITS), BITS)
    itiles = ops.precompute_tiles_int(B0, b, chvs, W=FRAME, w=FRAG,
                                      stride=STRIDE, block_d=BLOCK_D)
    a = np.asarray(ops.fragment_score_map_batch_int(
        codes, chvs, B0, b, h=FRAG, w=FRAG, stride=STRIDE, tiles=itiles))
    jax.clear_caches()
    b_ = np.asarray(ops.fragment_score_map_batch_int(
        codes, chvs, B0, b, h=FRAG, w=FRAG, stride=STRIDE, tiles=itiles))
    return {"bitwise_equal": bool((a == b_).all())}


def run(n_frames: int = CHUNK, reps: int = 8,
        backend: str = "pallas") -> list[dict]:
    """Benchmark-driver entry point (``python -m benchmarks.run``)."""
    t = throughput(n_frames, reps)
    lw = large_w_check()
    a = auc_parity(backend)
    bc = binary_curve(backend)
    d = determinism()
    return [
        {"name": "int_datapath/throughput",
         "float_fps": f"{t['float_fps']:.1f}",
         "int8_fps": f"{t['int8_fps']:.1f}",
         "expanded_fps": f"{t['expanded_fps']:.1f}",
         "speedup": f"{t['speedup']:.2f}x",
         "speedup_vs_expanded": f"{t['speedup_vs_expanded']:.2f}x",
         "chunk": t["chunk"]},
        {"name": "int_datapath/large_w",
         "W": lw["W"],
         "oracle_max_err": f"{lw['oracle_max_err']:.2e}",
         "guard_ok": lw["guard_ok"],
         "expanded_would_fit": lw["expanded_would_fit"]},
        {"name": "int_datapath/auc",
         "synthetic_float": f"{a['synthetic_float_auc']:.4f}",
         "synthetic_int8": f"{a['synthetic_int8_auc']:.4f}",
         "synthetic_gap": f"{a['synthetic_gap']:.4f}",
         "synthetic_int4": f"{a['synthetic_int4_auc']:.4f}",
         "synthetic_int4_gap": f"{a['synthetic_int4_gap']:.4f}",
         "drift_float": f"{a['drift_float_auc']:.4f}",
         "drift_int8": f"{a['drift_int8_auc']:.4f}",
         "drift_gap": f"{a['drift_gap']:.4f}",
         "drift_int4": f"{a['drift_int4_auc']:.4f}",
         "drift_int4_gap": f"{a['drift_int4_gap']:.4f}",
         "backend": a["backend"]},
        {"name": "int_datapath/binary_curve",
         **{f"d{dim}": f"{bc[f'd{dim}_binary_auc']:.4f}"
            for dim in BINARY_DIMS},
         **{f"d{dim}_float": f"{bc[f'd{dim}_float_auc']:.4f}"
            for dim in BINARY_DIMS},
         "best": f"{bc['best_binary_auc']:.4f}"},
        {"name": "int_datapath/determinism",
         "bitwise_equal": d["bitwise_equal"]},
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=CHUNK,
                    help="chunk size (>= 8 is the claimed regime)")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--backend", default="pallas",
                    choices=["jnp", "pallas"],
                    help="backend for the AUC scenarios")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless int8 fps >= float fps AND "
                         ">= the expanded-slab baseline at chunk >= 8, "
                         f"AUC gaps <= {AUC_TOL} for int8 and int4, the "
                         f"binary curve peaks >= {BINARY_MIN_BEST_AUC}, "
                         "the large-W kernel matches its oracle, and the "
                         "int path is bitwise deterministic")
    args = ap.parse_args()

    rows = run(args.frames, args.reps, args.backend)
    vals = {}
    for row in rows:
        name = row.pop("name")
        vals[name] = dict(row)
        print(name + "," + ",".join(f"{k}={v}" for k, v in row.items()))

    if args.check:
        t = vals["int_datapath/throughput"]
        lw = vals["int_datapath/large_w"]
        a = vals["int_datapath/auc"]
        bc = vals["int_datapath/binary_curve"]
        d = vals["int_datapath/determinism"]
        if float(t["int8_fps"]) < float(t["float_fps"]):
            raise SystemExit(
                f"REGRESSION: int8 path {t['int8_fps']} fps < float path "
                f"{t['float_fps']} fps at chunk {t['chunk']}")
        if float(t["int8_fps"]) < float(t["expanded_fps"]):
            raise SystemExit(
                f"REGRESSION: rolling-shift kernel {t['int8_fps']} fps < "
                f"expanded-slab baseline {t['expanded_fps']} fps at chunk "
                f"{t['chunk']} — the VMEM fix must not cost throughput")
        # the integer projection core is exact; the float cosine epilogue
        # reduces in a different order than the jnp oracle, so the match
        # is tolerance-level, not bitwise (determinism is gated separately)
        if float(lw["oracle_max_err"]) > 1e-6:
            raise SystemExit(
                f"REGRESSION: large-W (W={lw['W']}) kernel deviates from "
                f"the oracle by {lw['oracle_max_err']}")
        if lw["expanded_would_fit"] not in (False, "False"):
            raise SystemExit(
                "REGRESSION: byte model claims the expanded layout fits "
                "deployment scale — the working-set regression is gone")
        for scen in ("synthetic", "drift"):
            if float(a[f"{scen}_gap"]) > AUC_TOL:
                raise SystemExit(
                    f"REGRESSION: int8 AUC gap {a[f'{scen}_gap']} > "
                    f"{AUC_TOL} on the {scen} scenario "
                    f"(float {a[f'{scen}_float']}, int8 "
                    f"{a[f'{scen}_int8']})")
            if float(a[f"{scen}_int4_gap"]) > AUC_TOL:
                raise SystemExit(
                    f"REGRESSION: int4 AUC gap {a[f'{scen}_int4_gap']} > "
                    f"{AUC_TOL} on the {scen} scenario "
                    f"(int4 {a[f'{scen}_int4']})")
        if float(bc["best"]) < BINARY_MIN_BEST_AUC:
            raise SystemExit(
                f"REGRESSION: binary gate's best AUC {bc['best']} < "
                f"{BINARY_MIN_BEST_AUC} anywhere on D in {BINARY_DIMS}")
        if d["bitwise_equal"] is not True and d["bitwise_equal"] != "True":
            raise SystemExit("REGRESSION: int path not bitwise "
                             "deterministic across runs")
        print("int_datapath/check,ok=True")


if __name__ == "__main__":
    main()
