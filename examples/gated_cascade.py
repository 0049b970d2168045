"""The paper's full loop: HDC gate → HP capture → backbone detector.

A closed-loop StreamRunner gates a sparse-event radar stream, its
high-precision burst drains feed a CascadeService backbone, and the
capture log bills the whole system against an always-on detector.

Run:  PYTHONPATH=src python examples/gated_cascade.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import encoding, hypersense
from repro.core.sensor_control import CaptureConfig, ControllerConfig
from repro.launch import steps
from repro.launch.cascade import CascadeService
from repro.launch.compile_cache import enable_compile_cache
from repro.sensing import synthetic
from repro.sensing.stream import StreamRunner

FRAME, CHUNK, BATCH = 32, 16, 8


def main() -> None:
    enable_compile_cache()
    # a tiny gate (untrained weights are fine for the plumbing demo);
    # threshold at the open-loop score q75 so only score peaks fire
    # (closed-loop decimation skips idle frames, thinning high scores)
    B0, b = encoding.make_perm_base_rows(jax.random.PRNGKey(1), 8, 256)
    gate = hypersense.HyperSenseModel(
        jax.random.normal(jax.random.PRNGKey(2), (2, 256)), B0, b,
        h=8, w=8, stride=4, t_score=0.0, t_detection=1)
    stream, _ = synthetic.make_drift_stream(
        jax.random.PRNGKey(3), 8 * CHUNK,
        synthetic.RadarConfig(height=FRAME, width=FRAME),
        event_prob=0.03, event_len=10)
    stream = np.asarray(stream)
    scores = hypersense.frame_scores_batch(gate, stream, 0,
                                           sequential=True)
    gate = gate._replace(t_score=float(np.quantile(scores, 0.75)))
    runner = StreamRunner(gate,
                          ControllerConfig(base_rate_hz=10.0,
                                           active_rate_hz=30.0,
                                           hold_frames=4),
                          chunk_size=CHUNK,
                          control=CaptureConfig(hp_bits=12))

    # the downstream detector: smoke embeds-in backbone + patch embedder
    cfg = configs.get_smoke("hubert-xlarge")
    params = steps.init_detector_params(jax.random.PRNGKey(7), cfg,
                                        frame_hw=(FRAME, FRAME), patch=8)
    casc = CascadeService(params, cfg, batch_size=BATCH,
                          frame_hw=(FRAME, FRAME))

    for t in range(0, len(stream), CHUNK):
        runner.process(stream[t:t + CHUNK])
        casc.pump(runner)                 # ragged drain -> fixed batches
    for batch in casc.flush():
        for i, logit in zip(batch.frame_idx, batch.logits):
            label = int(jnp.argmax(jnp.asarray(logit)))
            print(f"frame {int(i):4d}  detector class {label}  "
                  f"logits {np.round(logit, 3)}")

    log = runner.capture_log
    e = casc.system_energy(log)
    duty = float(np.asarray(log.gated, bool).mean())
    print(f"\ngate duty cycle      {duty:.3f}")
    print(f"backbone compiles    {casc.compile_count()} "
          f"(ragged drains, fixed shapes)")
    print(f"cascade   J/frame    {e['cascade'].total:.4f}")
    print(f"always-on J/frame    {e['always_on'].total:.4f}  "
          f"(saving {1 - e['cascade'].total / e['always_on'].total:.1%})")


if __name__ == "__main__":
    main()
