"""Quickstart: train a HyperSense fragment model and score a frame.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fragment_model as fm
from repro.core import hypersense, metrics
from repro.core.encoding import encode_fragments
from repro.launch.compile_cache import enable_compile_cache
from repro.sensing import adc, fragments, synthetic


def main() -> None:
    enable_compile_cache()
    key = jax.random.PRNGKey(0)

    # 1. sense: synthetic radar frames through the low-precision ADC path
    cfg = synthetic.RadarConfig(height=64, width=64)
    frames, masks, labels = synthetic.make_dataset(key, 80, cfg)
    frames_lp = adc.quantize(frames, bits=4)

    # 2. fragment dataset (balanced positives/negatives)
    frags, flabels = fragments.sample_fragments(
        np.asarray(frames_lp), np.asarray(masks), h=16, w=16,
        per_frame=2, seed=0)
    n = len(frags)
    tr, te = slice(0, int(n * 0.8)), slice(int(n * 0.8), n)

    # 3. train the HDC Fragment model (bundling + retraining)
    model, info = fm.train_fragment_model(
        jax.random.PRNGKey(1), jnp.asarray(frags[tr]),
        jnp.asarray(flabels[tr]), dim=4096, epochs=10)
    print("retraining val accuracy:", [round(a, 3)
                                       for a in info["val_accuracy"]])

    # 4. fragment-level ROC
    hv = encode_fragments(jnp.asarray(frags[te]), model.B, model.b)
    scores = fm.positive_score(model.class_hvs, hv)
    fpr, tpr, _ = metrics.roc_curve(np.asarray(scores), flabels[te])
    print(f"fragment AUC: {metrics.auc(fpr, tpr):.3f}")

    # 5. frame-level HyperSense detection (sliding window, reuse encoder)
    B0 = model.B.reshape(16, 16, -1)[:, 0, :]
    hs = hypersense.from_fragment_model(model, B0, h=16, w=16, stride=8,
                                        t_score=0.0, t_detection=0)
    decisions = hypersense.detect_batch(hs, frames_lp[:16])
    print("frame decisions:", np.asarray(decisions).astype(int))
    print("frame labels:   ", np.asarray(labels[:16]))


if __name__ == "__main__":
    main()
