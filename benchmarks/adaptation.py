"""Online adaptation: drifted-stream recovery + classifier-update cost.

The two claims behind the online-learning runtime (ISSUE 3):

* ``retile-vs-precompute`` — installing an updated classifier into the
  scoring kernel is a jitted device-side gather
  (:func:`repro.kernels.ops.retile_classes` against a cached
  :class:`~repro.kernels.sliding_scores.ScoreGeometry`), far cheaper than
  the full host-side ``precompute_tiles`` (which rebuilds the slabs, the
  rotation index and the bias tiles nobody changed). ``--check`` enforces
  ``retile <= precompute / 2``.

* ``drift-recovery`` — on a synthetic stream whose background gain, noise
  sigma and object intensity drift away from the training distribution
  (:func:`repro.sensing.synthetic.make_drift_stream`), an adaptive runner
  (label feedback, the paper's similarity-scaled perceptron rule applied
  to each frame's top-scoring fragment) recovers frame-score AUC on the
  drifted half of the stream, while the frozen model degrades. ``--check``
  enforces ``adaptive late-AUC >= frozen late-AUC``.

Also reported (not enforced): the confidence-gated pseudo-label mode and
the wall-clock overhead of adaptation per processed frame.

Everything is seeded; on CPU the numbers are deterministic.

Run:  PYTHONPATH=src python benchmarks/adaptation.py [--check]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fragment_model as fm, hypersense, metrics
from repro.core.encoding import make_perm_base_rows
from repro.core.online import AdaptConfig
from repro.core.sensor_control import ControllerConfig
from repro.kernels import ops
from repro.launch.replay import StreamRunner
from repro.sensing import fragments, synthetic

# CPU-tractable scale; the drift scenario is chosen so the frozen model
# genuinely degrades (late AUC ~0.73 here) and label feedback measurably
# recovers (~0.78) — deterministic under the fixed seeds.
FRAME = 32
FRAG = 8
STRIDE = 4
DIM = 1024
N_STREAM = 200
CHUNK = 16
LR = 2.0

# retile timing at deployment-like scale (bigger model than the AUC demo:
# the precompute/retile gap is the per-model-size claim)
T_FRAG, T_DIM, T_W, T_BLOCK = 16, 4096, 128, 512


def _best(fn, reps: int) -> float:
    fn()  # warmup: jit compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def time_retile(reps: int = 5) -> dict:
    """Classifier-update cost: full host precompute vs device retile."""
    B0, b = make_perm_base_rows(jax.random.PRNGKey(0), T_FRAG, T_DIM)
    chvs = jax.random.normal(jax.random.PRNGKey(1), (2, T_DIM))
    geom = ops.precompute_geometry(B0, b, W=T_W, w=T_FRAG, stride=8,
                                   block_d=T_BLOCK)
    t_pre = _best(lambda: jax.block_until_ready(
        ops.precompute_tiles(B0, b, chvs, W=T_W, w=T_FRAG, stride=8,
                             block_d=T_BLOCK)), reps)
    t_ret = _best(lambda: jax.block_until_ready(
        ops.retile_classes(geom, chvs)), reps)
    return {"precompute_ms": t_pre * 1e3, "retile_ms": t_ret * 1e3,
            "speedup": t_pre / t_ret}


def _train_gate(cfg):
    """Fragment model on the *clean* (pre-drift) distribution."""
    frames, masks, _ = synthetic.make_dataset(jax.random.PRNGKey(0), 60,
                                              cfg)
    frs, labs = fragments.sample_fragments(
        np.asarray(frames), np.asarray(masks), h=FRAG, w=FRAG,
        per_frame=2, seed=0)
    model, _ = fm.train_fragment_model(
        jax.random.PRNGKey(1), jnp.asarray(frs), jnp.asarray(labs),
        dim=DIM, epochs=8)
    B0 = model.B.reshape(FRAG, FRAG, -1)[:, 0, :]
    return hypersense.from_fragment_model(model, B0, h=FRAG, w=FRAG,
                                          stride=STRIDE, t_detection=1)


def _auc(scores, labels) -> float:
    fpr, tpr, _ = metrics.roc_curve(scores, labels)
    return float(metrics.auc(fpr, tpr))


def drift_recovery(backend: str = "jnp") -> dict:
    """Frozen vs adaptive frame-score AUC on the drifted half."""
    cfg = synthetic.RadarConfig(height=FRAME, width=FRAME)
    hs = _train_gate(cfg)
    drift = synthetic.DriftConfig(background_gain=(0.0, 0.7),
                                  noise_sigma=(0.12, 0.3),
                                  object_intensity=(0.8, 0.3))
    stream, labels = synthetic.make_drift_stream(
        jax.random.PRNGKey(3), N_STREAM, cfg, drift, event_prob=0.06,
        event_len=10)
    labels = np.asarray(labels)
    half = N_STREAM // 2
    control = ControllerConfig(hold_frames=2)

    def timed(feed, **kw):
        # warmup on a throwaway runner: the step's jit cache is shared
        StreamRunner(hs, control, chunk_size=CHUNK, backend=backend,
                     **kw).process(
            stream[:CHUNK], labels=None if feed is None else feed[:CHUNK])
        runner = StreamRunner(hs, control, chunk_size=CHUNK,
                              backend=backend, **kw)
        t0 = time.perf_counter()
        out = runner.process(stream, labels=feed)
        return out, time.perf_counter() - t0

    (s_frozen, _, _), t_frozen = timed(None)
    (s_label, _, _), t_label = timed(
        labels, adapt=AdaptConfig(mode="label", lr=LR))
    (s_pseudo, _, _), _ = timed(
        None, adapt=AdaptConfig(mode="pseudo", lr=0.5, confidence=0.02))

    return {
        "frozen_auc_late": _auc(s_frozen[half:], labels[half:]),
        "label_auc_late": _auc(s_label[half:], labels[half:]),
        "pseudo_auc_late": _auc(s_pseudo[half:], labels[half:]),
        "frozen_auc_early": _auc(s_frozen[:half], labels[:half]),
        "adapt_overhead_ms_per_frame":
            (t_label - t_frozen) / N_STREAM * 1e3,
        "backend": backend,
    }


def run(backend: str = "jnp", reps: int = 5) -> list[dict]:
    """Benchmark-driver entry point (``python -m benchmarks.run``)."""
    t = time_retile(reps)
    r = drift_recovery(backend)
    return [
        {"name": "adaptation/retile",
         "precompute_ms": f"{t['precompute_ms']:.2f}",
         "retile_ms": f"{t['retile_ms']:.2f}",
         "speedup": f"{t['speedup']:.1f}x"},
        {"name": "adaptation/drift",
         "frozen_early": f"{r['frozen_auc_early']:.4f}",
         "frozen_late": f"{r['frozen_auc_late']:.4f}",
         "label_late": f"{r['label_auc_late']:.4f}",
         "pseudo_late": f"{r['pseudo_auc_late']:.4f}",
         "overhead_ms_per_frame":
             f"{r['adapt_overhead_ms_per_frame']:.3f}",
         "backend": r["backend"]},
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless retile <= precompute/2 AND "
                         "adaptive late-AUC >= frozen late-AUC under "
                         "drift (the online-learning claims)")
    args = ap.parse_args()

    rows = run(args.backend, args.reps)
    vals = {}
    for row in rows:
        name = row.pop("name")
        vals[name] = dict(row)
        print(name + "," + ",".join(f"{k}={v}" for k, v in row.items()))

    if args.check:
        t = vals["adaptation/retile"]
        r = vals["adaptation/drift"]
        if float(t["retile_ms"]) > float(t["precompute_ms"]) / 2:
            raise SystemExit(
                f"REGRESSION: retile_classes {t['retile_ms']} ms not "
                f"<= precompute_tiles/2 ({t['precompute_ms']} ms / 2)")
        if float(r["label_late"]) < float(r["frozen_late"]):
            raise SystemExit(
                f"REGRESSION: adaptive late-AUC {r['label_late']} < "
                f"frozen late-AUC {r['frozen_late']} under drift")
        print("adaptation/check,ok=True")


if __name__ == "__main__":
    main()
