"""Closed-loop sensor control: ADC conversions saved at matched quality.

The paper's headline mechanism (§III-B): HyperSense "controls the ADC
modules' data generation rate based on object presence predictions". The
closed-loop runtime (``StreamRunner(control=CaptureConfig(...))``) makes
the ``ControllerConfig`` rates real — idle frames are LP-converted at
``base_rate_hz`` only, gate bursts capture every frame and turn on the
high-precision path. Two claims, both enforced by ``--check``:

* ``samples`` — on a sparse-event synthetic stream the closed loop
  converts **>= 2x fewer ADC samples** than always-on capture *at matched
  missed_positive*: the always-on baseline is swept over its score
  threshold and compared at the operating point with the fewest
  conversions whose missed-positive rate is still no worse than the
  closed loop's (i.e. the baseline gets every benefit of the doubt — it
  just can never stop converting the idle frames).
* ``parity`` — with control *disabled* (``subsample=False``, and
  separately ``base_rate_hz == active_rate_hz``) the closed-loop runner's
  scores/fired/gated are **bitwise identical** to the open-loop runner:
  the control plumbing costs nothing when it is off.

Also reported: the capture-log energy account
(:func:`repro.core.energy.from_capture_log`) for both regimes — the
closed loop's savings are billed from conversions actually made, not a
duty-cycle approximation.

Run:  PYTHONPATH=src python benchmarks/control_loop.py [--check]
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy, fragment_model as fm, hypersense, metrics
from repro.core.sensor_control import (CaptureConfig, CaptureLog,
                                       ControllerConfig, decimation,
                                       stats_from)
from repro.launch.replay import StreamRunner
from repro.sensing import fragments, synthetic
from repro.sensing.stream import gate_scan

# CPU-tractable scale: sparse events (the paper's "activity of interest
# is infrequent" regime) so idle decimation has something to save.
FRAME = 32
FRAG = 8
STRIDE = 4
DIM = 1024
N_STREAM = 400
CHUNK = 32
EVENT_PROB = 0.008
EVENT_LEN = 12
BASE_HZ = 10.0
ACTIVE_HZ = 60.0
HOLD = 6


def _train_gate(cfg):
    """Small Fragment-model gate at an FPR-targeted operating point."""
    frames, masks, _ = synthetic.make_dataset(jax.random.PRNGKey(0), 60,
                                              cfg)
    frs, labs = fragments.sample_fragments(
        np.asarray(frames), np.asarray(masks), h=FRAG, w=FRAG,
        per_frame=2, seed=0)
    model, _ = fm.train_fragment_model(
        jax.random.PRNGKey(1), jnp.asarray(frs), jnp.asarray(labs),
        dim=DIM, epochs=8)
    B0 = model.B.reshape(FRAG, FRAG, -1)[:, 0, :]
    hs = hypersense.from_fragment_model(model, B0, h=FRAG, w=FRAG,
                                        stride=STRIDE, t_detection=1)
    te_frames, _, te_labels = synthetic.make_dataset(
        jax.random.PRNGKey(2), 32, cfg)
    scores = np.asarray(hypersense.frame_scores_batch(hs, te_frames, 0,
                                                      sequential=True))
    fpr, tpr, thr = metrics.roc_curve(scores, np.asarray(te_labels))
    t_score = metrics.threshold_at_fpr(fpr, tpr, thr, 0.1)
    return hs._replace(t_score=float(t_score))


def _samples(log) -> int:
    return log.samples_converted()


def _matched_always_on(scores, labels, hold: int, target_missed: float,
                       pixels: int
                       ) -> tuple[int, float, float, np.ndarray]:
    """Cheapest always-on operating point no worse than the closed loop:
    ``(samples_converted, duty, missed_positive, gated)``.

    The always-on runner's scores are threshold-independent, so the sweep
    replays ``gate_scan`` per candidate threshold — no re-scoring. Picks
    the point with the fewest total conversions (LP every frame + HP on
    gated frames) whose ``missed_positive <= target``; always exists
    because gating everything misses nothing. Rates come from the same
    :func:`~repro.core.sensor_control.stats_from` accounting as the
    closed-loop side of the comparison (so an event-free stream — NaN
    target — is rejected up front, not silently matched).
    """
    if not np.isfinite(target_missed):
        raise SystemExit(
            "control_loop benchmark stream has no positive frames "
            "(missed_positive is NaN) — matched comparison is undefined; "
            "raise EVENT_PROB / N_STREAM")
    best = None
    for t in np.unique(np.asarray(scores)):
        for cand in (t, np.nextafter(t, -np.inf)):
            fired = np.asarray(scores) > cand
            gated = np.asarray(gate_scan(jnp.asarray(fired), hold)[0])
            stats = stats_from(fired, gated, labels)
            if stats.missed_positive <= target_missed + 1e-12:
                samples = (len(labels) + int(gated.sum())) * pixels
                if best is None or samples < best[0]:
                    best = (samples, stats.duty_cycle,
                            stats.missed_positive, gated)
    return best


def run(backend: str = "jnp") -> list[dict]:
    cfg = synthetic.RadarConfig(height=FRAME, width=FRAME)
    hs = _train_gate(cfg)
    stream, labels = synthetic.make_drift_stream(
        jax.random.PRNGKey(3), N_STREAM, cfg, synthetic.DriftConfig(),
        event_prob=EVENT_PROB, event_len=EVENT_LEN)
    labels = np.asarray(labels)
    control = ControllerConfig(base_rate_hz=BASE_HZ,
                               active_rate_hz=ACTIVE_HZ,
                               hold_frames=HOLD)
    pixels = FRAME * FRAME

    # --- closed loop -----------------------------------------------------
    closed = StreamRunner(hs, control, chunk_size=CHUNK, backend=backend,
                          control=CaptureConfig(hp_buffer=0))
    _, fired_c, gated_c = closed.process(stream)
    log_c = closed.capture_log
    stats_c = stats_from(fired_c, gated_c, labels)
    e_closed = energy.from_capture_log(log_c)

    # --- always-on baseline at matched missed_positive -------------------
    always = StreamRunner(hs, control, chunk_size=CHUNK, backend=backend)
    scores_a, fired_a, gated_a = always.process(stream)
    samples_a, duty_a, missed_a, gated_m = _matched_always_on(
        scores_a, labels, HOLD, stats_c.missed_positive, pixels)
    # bill the baseline AT the matched operating point (every frame
    # LP-converted, the matched threshold's gate pattern HP-converted)
    e_always = energy.from_capture_log(CaptureLog(
        sampled=np.ones_like(gated_m), gated=gated_m,
        frame_pixels=pixels))

    reduction = samples_a / max(_samples(log_c), 1)

    # --- parity: the closed loop off == the open loop --------------------
    off = StreamRunner(hs, control, chunk_size=CHUNK, backend=backend,
                       control=CaptureConfig(subsample=False, hp_buffer=0))
    s_off, f_off, g_off = off.process(stream)
    flat = ControllerConfig(base_rate_hz=ACTIVE_HZ,
                            active_rate_hz=ACTIVE_HZ, hold_frames=HOLD)
    same = StreamRunner(hs, flat, chunk_size=CHUNK, backend=backend,
                        control=CaptureConfig(hp_buffer=0))
    s_same, f_same, g_same = same.process(stream)
    parity = bool((s_off == scores_a).all() and (f_off == fired_a).all()
                  and (g_off == gated_a).all()
                  and (s_same == scores_a).all()
                  and (f_same == fired_a).all()
                  and (g_same == gated_a).all())

    return [
        {"name": "control_loop/closed",
         "samples_converted": _samples(log_c),
         "sampled_frac": f"{float(log_c.sampled.mean()):.3f}",
         "duty": f"{stats_c.duty_cycle:.3f}",
         "missed_positive": f"{stats_c.missed_positive:.3f}",
         "energy_j_per_frame": f"{e_closed.total:.4f}",
         "decim": decimation(control), "backend": backend},
        {"name": "control_loop/always_on_matched",
         "samples_converted": samples_a,
         "duty": f"{duty_a:.3f}",
         "missed_positive": f"{missed_a:.3f}",
         "energy_j_per_frame": f"{e_always.total:.4f}",
         "backend": backend},
        {"name": "control_loop/samples_reduction",
         "value": f"{reduction:.2f}x", "backend": backend},
        {"name": "control_loop/parity_when_disabled",
         "bitwise_equal": parity, "backend": backend},
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless the closed loop converts "
                         ">= 2x fewer ADC samples than the matched "
                         "always-on baseline AND disabling control is "
                         "bitwise-invisible")
    args = ap.parse_args()

    rows = run(args.backend)
    vals = {}
    for row in rows:
        name = row.pop("name")
        vals[name] = dict(row)
        print(name + "," + ",".join(f"{k}={v}" for k, v in row.items()))

    if args.check:
        red = float(vals["control_loop/samples_reduction"]["value"][:-1])
        if red < 2.0:
            raise SystemExit(
                f"REGRESSION: closed-loop samples reduction {red:.2f}x "
                f"< 2x vs matched always-on capture")
        if vals["control_loop/parity_when_disabled"]["bitwise_equal"] \
                is not True:
            raise SystemExit(
                "REGRESSION: closed-loop runner with control disabled is "
                "not bitwise-identical to the open-loop runner")
        print("control_loop/check,ok=True")


if __name__ == "__main__":
    main()
