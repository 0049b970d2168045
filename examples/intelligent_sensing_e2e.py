"""End-to-end Intelligent Sensor Control (the paper's full pipeline).

sensor stream -> low-precision ADC -> HDC HyperSense gate -> high-precision
path + "cloud model" only when gated on -> energy accounting (Fig. 17).

Single-sensor by default; ``--sensors S`` runs the same trained gate over
S concurrent streams through the fleet runtime
(:mod:`repro.sensing.fleet`): every super-chunk is scored in one batched
step (one kernel launch on ``--backend pallas``), each stream keeps its
own controller hysteresis, and the energy account aggregates the fleet.
The ADC sits *inside* the runtime (``adc_bits=4``) — the gate scores the
cheap 4-bit capture while the raw high-precision frames stand in for what
the gated-on path would deliver.

Run:  PYTHONPATH=src python examples/intelligent_sensing_e2e.py [--sensors 4]
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy, fragment_model as fm, hypersense, metrics
from repro.core.online import AdaptConfig
from repro.core.sensor_control import (CaptureConfig, ControllerConfig,
                                       decimation, stats_from)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.replay import (StreamRunner, simulate_fleet,
                                 simulate_stream_batched)
from repro.sensing import adc, fragments, synthetic


def train_gate(key, cfg, frag, dim, stride):
    """Train the Fragment model on low-precision captures and pick the
    operating T_score for a target FPR (paper §III-C)."""
    frames, masks, _ = synthetic.make_dataset(key, 60, cfg)
    frames_lp = adc.quantize(frames, 4)
    frs, labs = fragments.sample_fragments(
        np.asarray(frames_lp), np.asarray(masks), h=frag, w=frag,
        per_frame=2, seed=0)
    model, _ = fm.train_fragment_model(
        jax.random.PRNGKey(1), jnp.asarray(frs), jnp.asarray(labs),
        dim=dim, epochs=10)
    B0 = model.B.reshape(frag, frag, -1)[:, 0, :]

    te_frames, _, te_labels = synthetic.make_dataset(
        jax.random.PRNGKey(2), 24, cfg)
    te_lp = adc.quantize(te_frames, 4)
    hs = hypersense.from_fragment_model(model, B0, h=frag, w=frag,
                                        stride=stride)
    scores = np.asarray(hypersense.frame_scores_batch(hs, te_lp, 0,
                                                      sequential=True))
    fpr, tpr, thr = metrics.roc_curve(scores, np.asarray(te_labels))
    target_fpr = 0.1
    t_score = metrics.threshold_at_fpr(fpr, tpr, thr, target_fpr)
    print(f"operating point: FPR<={target_fpr} -> T_score={t_score:.4f} "
          f"TPR={metrics.tpr_at_fpr(fpr, tpr, target_fpr):.3f}")
    return hs._replace(t_score=float(t_score))


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--sensors", type=int, default=1,
                    help="number of concurrent sensor streams (>1 uses "
                         "the fleet runtime)")
    ap.add_argument("--frames", type=int, default=150,
                    help="stream length per sensor")
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--drift", action="store_true",
                    help="drifting single-sensor stream: frozen gate vs "
                         "online adaptation (label feedback + pseudo)")
    ap.add_argument("--control", action="store_true",
                    help="close the capture loop: idle frames trickle at "
                         "base_rate_hz, gate bursts capture at "
                         "active_rate_hz + high precision; energy billed "
                         "from the capture log")
    args = ap.parse_args()

    if args.control:
        # --- gate-driven variable-rate/-precision capture ----------------
        cfg = synthetic.RadarConfig(height=32, width=32)
        hs = train_gate(jax.random.PRNGKey(0), cfg, 8, 1024, 4)
        rates = ControllerConfig(base_rate_hz=10, active_rate_hz=60,
                                 hold_frames=6)
        stream, labels = synthetic.make_stream(
            jax.random.PRNGKey(3), args.frames, cfg, event_prob=0.01,
            event_len=12)
        labels = np.asarray(labels)
        runner = StreamRunner(hs, rates, chunk_size=32,
                              backend=args.backend, adc_bits=4,
                              control=CaptureConfig(hp_bits=12))
        _, fired, gated = runner.process(stream)
        stats = stats_from(fired, gated, labels)
        log = runner.capture_log
        hp_idx, hp_frames = runner.drain_hp()
        print(f"closed loop (decim {decimation(rates)}): "
              f"LP-converted {int(log.sampled.sum())}/{len(stream)} "
              f"frames, duty {stats.duty_cycle:.3f}, "
              f"missed {stats.missed_positive:.3f}")
        print(f"HP deliverable: {len(hp_idx)} burst frames at "
              f"{log.hp_bits} bits (dropped {runner.hp_dropped})")
        ours = energy.from_capture_log(log)
        always = energy.hypersense_measured(stats.duty_cycle)
        conv = energy.conventional()
        print(f"energy/frame from capture log: {ours.total:.3f} J "
              f"(always-on LP estimate {always.total:.3f} J, "
              f"conventional {conv.total:.3f} J) -> "
              f"saving {1 - ours.total / conv.total:.1%}")
        return

    if args.drift:
        # --- online learning under distribution drift -------------------
        # CPU-tractable scale (three full runner passes over the stream)
        cfg = synthetic.RadarConfig(height=32, width=32)
        hs = train_gate(jax.random.PRNGKey(0), cfg, 8, 1024, 4)
        control = ControllerConfig(hold_frames=3)
        drift = synthetic.DriftConfig(background_gain=(0.0, 0.6),
                                      noise_sigma=(0.12, 0.28),
                                      object_intensity=(0.8, 0.35))
        stream, labels = synthetic.make_drift_stream(
            jax.random.PRNGKey(3), args.frames, cfg, drift,
            event_prob=0.05, event_len=10)
        labels = np.asarray(labels)
        half = len(labels) // 2

        def late_auc(scores):
            fpr, tpr, _ = metrics.roc_curve(scores[half:], labels[half:])
            return metrics.auc(fpr, tpr)

        frozen = StreamRunner(hs, control, chunk_size=32,
                              backend=args.backend)
        s_f, _, _ = frozen.process(stream)
        ada = StreamRunner(hs, control, chunk_size=32,
                           backend=args.backend,
                           adapt=AdaptConfig(mode="label", lr=2.0))
        s_a, _, _ = ada.process(stream, labels=labels)
        pseudo = StreamRunner(hs, control, chunk_size=32,
                              backend=args.backend,
                              adapt=AdaptConfig(mode="pseudo", lr=0.5,
                                                confidence=0.02))
        s_p, _, _ = pseudo.process(stream)
        print(f"drifted-half frame-score AUC: frozen {late_auc(s_f):.3f}, "
              f"label-feedback {late_auc(s_a):.3f}, "
              f"pseudo-label {late_auc(s_p):.3f}")
        return

    cfg = synthetic.RadarConfig(height=64, width=64)
    frag, dim, stride = 16, 2048, 8
    hs = train_gate(jax.random.PRNGKey(0), cfg, frag, dim, stride)
    control = ControllerConfig(hold_frames=3)

    if args.sensors <= 1:
        # --- single stream through the chunked runtime ------------------
        stream, stream_labels = synthetic.make_stream(
            jax.random.PRNGKey(3), args.frames, cfg, event_prob=0.03,
            event_len=10)
        stats = simulate_stream_batched(hs, stream,
                                        np.asarray(stream_labels),
                                        control, chunk_size=32,
                                        backend=args.backend, adc_bits=4)
        print(f"stream: duty cycle {stats.duty_cycle:.3f}, "
              f"missed positives {stats.missed_positive:.3f}, "
              f"false active {stats.false_active:.3f}")
        if not np.isfinite(stats.missed_positive):
            print("stream drew no object events (missed_positive is "
                  "undefined) — rerun with more --frames for the energy "
                  "account")
            return

        params = energy.calibrate()
        conv = energy.conventional(params)
        p_obj = float(np.mean(stream_labels))
        ours = energy.hypersense(stats.false_active,
                                 1.0 - stats.missed_positive, p_obj,
                                 params)
        s = energy.savings(ours, conv)
        print(f"p(object)={p_obj:.3f}: total energy saving "
              f"{s['total_saving']:.1%}, edge saving "
              f"{s['edge_saving']:.1%}, quality loss "
              f"{stats.missed_positive:.2%}")
        print("(paper @FPR0.1: total 89.8%, edge 60.6%, QL 4.93%)")
        return

    # --- sensor fleet: S streams, one batched runtime -------------------
    streams, labels = [], []
    for s in range(args.sensors):
        fr, lb = synthetic.make_stream(
            jax.random.fold_in(jax.random.PRNGKey(3), s), args.frames,
            cfg, event_prob=0.03, event_len=10)
        streams.append(fr)
        labels.append(np.asarray(lb))
    fleet_frames = jnp.stack(streams)
    fleet_labels = np.stack(labels)

    report = simulate_fleet(hs, fleet_frames, fleet_labels, control,
                            chunk_size=32, backend=args.backend,
                            adc_bits=4,
                            energy_params=energy.calibrate())
    for s, st in enumerate(report.stats):
        print(f"sensor {s}: duty {st.duty_cycle:.3f}, "
              f"missed {st.missed_positive:.3f}, "
              f"false-active {st.false_active:.3f}")
    print(f"fleet of {report.n_sensors} x {report.n_frames} frames: "
          f"mean duty cycle {report.duty_cycle:.3f}")
    print(f"fleet energy: {report.energy_total_j:.1f} J vs always-on "
          f"{report.baseline_total_j:.1f} J "
          f"-> total saving {report.total_saving:.1%}")


if __name__ == "__main__":
    main()
