"""Whether what the timed path produced is correct.

Once the window has closed and the program's state is freed, the plain
reference (``bench/reference``) recomputes, from the raw frames the
traffic offered and the weights the benchmark made, every answer the
cell's layers give, and each number below is held to its limit from the
configuration file:

- ``score_gap`` and ``score_rms``: the largest and the root-mean-square
  |score - reference score| over every frame every sensor was served (ADC
  convert, scoring kernel, tile fold, cosine epilogue);
- ``decision_mismatch``: sensors whose fired / gated / sampled decisions
  depart from the reference controller's. A decision may flip only where
  the reference score lies within the configuration's ``decision_band``
  of ``t_score``;
  that sensor is compared up to the flip, since the flip changes what its
  closed loop samples next;
- ``hp_mismatch``: frames captured at high precision that the reference
  would not capture, or the other way round (compared region only);
- ``hp_gap``: the largest |HP frame - reference HP capture| over a sample
  of captured frames drawn from the seed;
- ``logit_gap`` and ``logit_rms``: over a sample of detected frames drawn
  from the seed, the largest and the root-mean-square |logit - reference
  logit|, over the RMS of the reference logits. The reference is the
  detector module the configuration names (``bench/reference``);
- ``logit_noise_ratio``: the RMS |logit - reference logit| over the RMS
  by which the reference's own logits move between float32 and bfloat16
  on the same frames. Random deep encoders differ from seed to seed in
  how much they amplify rounding; this ratio does not.

Only the numbers the configuration gives a limit are compared; the others
are printed as readings.

The control puts the reference, one precision lower, in the program's
place (:func:`control_outputs`); it has to fail one of the numbers.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from bench import generator, reference
from bench.reference import gate as gate_ref

#: detected frames the reference re-runs through the detector
LOGIT_SAMPLE = 64


@dataclasses.dataclass
class Outputs:
    """Answers of one side, per sensor over every frame served."""
    scores: np.ndarray        # (S, N)
    fired: np.ndarray
    gated: np.ndarray
    sampled: np.ndarray
    hp_idx: set               # {(sid, abs_idx)}
    hp_frames: dict           # (sid, abs_idx) -> frame
    logits: dict              # (sid, abs_idx) -> logits


def program_outputs(sess) -> Outputs:
    cat = lambda blocks: np.concatenate(blocks, axis=1)
    hp = {(sid, int(i)) for sid, idx in sess.hp_idx for i in idx}
    return Outputs(cat(sess.scores), cat(sess.fired), cat(sess.gated),
                   cat(sess.sampled), hp, dict(sess.hp_frames),
                   dict(sess.logits))


def _decim(sess):
    if not sess.t.capture:
        return None
    r = sess.cell.config["rates"]
    return max(1, int(round(r["active_rate_hz"] / r["base_rate_hz"])))


def _raw(sess, keys):
    """Raw pool frames of ``(sid, abs_idx)`` keys, ``(M, H, W)``."""
    if not keys:
        g = sess.g
        return np.zeros((0, g["frame_h"], g["frame_w"]), np.float32)
    out = []
    for sid, i in keys:
        stream, j = sess.replay.index(sid, i)
        out.append(sess.pool[stream, j])
    return np.stack(out)


class Reference:
    """The reference's answers for the frames one session served."""

    def __init__(self, sess, n_frames: int, precision: str = "highest"):
        self.sess = sess
        t, g = sess.t, sess.g
        pool_scores = (sess.pool_scores if precision == "highest" else
                       gate_ref.score_frames(sess.pool, sess.weights, g,
                                             precision=precision)
                       .reshape(t.pool_streams, t.pool_frames))
        self.scores = np.stack([
            pool_scores[sess.replay.index(s, np.arange(n_frames))]
            for s in range(t.sensors)])
        r = sess.cell.config["rates"]
        self.decisions = [gate_ref.control_scan(
            self.scores[s], sess.weights.t_score, r["hold_frames"],
            _decim(sess)) for s in range(t.sensors)]

    def outputs(self, logit_keys=(), mode: str = "bfloat16") -> Outputs:
        """The reference's own answers, in the program's place."""
        sess = self.sess
        fired, gated, sampled = (np.stack([d[k] for d in self.decisions])
                                 for k in range(3))
        hp = {(s, int(i)) for s in range(gated.shape[0])
              for i in np.flatnonzero(gated[s])} if sess.t.capture else set()
        keys = sorted(k for k in sess.hp_frames if k in hp)
        hp_frames = dict(zip(keys, self.hp(keys)))
        logits = dict(zip(logit_keys, self.logits(logit_keys, mode)))
        return Outputs(self.scores, fired, gated, sampled, hp, hp_frames,
                       logits)

    def hp(self, keys) -> np.ndarray:
        return gate_ref.quantize_hp(_raw(self.sess, keys),
                                    self.sess.g["adc_high_bits"])

    def logits(self, keys, mode: str = "bfloat16") -> np.ndarray:
        if not keys:
            return np.zeros((0, 0), np.float32)
        det = reference.detector(self.sess.cell.config)
        return det.logits(self.sess.det_params, self.hp(list(keys)),
                          self.sess.d, mode=mode)

    def bf16_noise(self, keys) -> float:
        """RMS of the reference's own float32 - bfloat16 logits on ``keys``:
        how far bfloat16 rounding moves this network's outputs."""
        if not hasattr(self, "_noise"):
            keys = list(keys)
            d = self.logits(keys, "float32") - self.logits(keys)
            self._noise = float(np.sqrt(np.mean(np.square(d))))
        return self._noise


def logit_sample(sess, out: Outputs) -> list:
    """Detected frames the check re-runs, drawn from the seed."""
    keys = sorted(out.logits)
    if len(keys) <= LOGIT_SAMPLE:
        return keys
    rng = generator.host_rng(sess.seed, 30)
    pick = rng.choice(len(keys), LOGIT_SAMPLE, replace=False)
    return [keys[i] for i in sorted(pick)]


def numbers(sess, out: Outputs, ref: Reference, ref_out: Outputs) -> dict:
    """The numbers compared, and how much each one covered."""
    tol = sess.cell.config["decision_band"]
    t = sess.weights.t_score
    S, N = ref.scores.shape
    res = {}
    s_out = out.scores[:, :N]
    bad = ~np.isfinite(s_out)
    gap = np.abs(s_out - ref.scores)
    res["score_gap"] = float(np.inf if bad.any() else gap.max())
    res["score_rms"] = float(np.inf if bad.any()
                             else np.sqrt(np.mean(np.square(gap))))
    mismatch, flips, ends = 0, 0, []
    for s in range(S):
        rf, rg, rs = ref_out.fired[s], ref_out.gated[s], ref_out.sampled[s]
        pf, pg, ps = out.fired[s, :N], out.gated[s, :N], out.sampled[s, :N]
        diff = np.flatnonzero(pf != rf)
        end = N
        if diff.size:
            end = int(diff[0])
            if abs(float(ref.scores[s, end]) - t) <= tol:
                flips += 1
            else:
                mismatch += 1
        if (pg[:end] != rg[:end]).any() or (ps[:end] != rs[:end]).any():
            mismatch += 1
        ends.append(end)
    res["decision_mismatch"] = mismatch
    cover = {"frames": int(S * N), "decisions": int(sum(ends)),
             "near_threshold_flips": flips}
    if sess.t.capture:
        inside = lambda k: k[1] < ends[k[0]]
        res["hp_mismatch"] = len({k for k in out.hp_idx if inside(k)}
                                 ^ {k for k in ref_out.hp_idx if inside(k)})
        keys = sorted(k for k in out.hp_frames if k in ref_out.hp_frames)
        res["hp_gap"] = max((float(np.max(np.abs(out.hp_frames[k]
                                                 - ref_out.hp_frames[k])))
                             for k in keys), default=0.0)
        cover["hp_frames"] = len(out.hp_idx)
        cover["hp_compared"] = len(keys)
    if sess.d is not None:
        keys = [k for k in ref_out.logits if k in out.logits]
        if keys:
            got = np.stack([out.logits[k] for k in keys])
            want = np.stack([ref_out.logits[k] for k in keys])
            rms = max(float(np.sqrt(np.mean(np.square(want)))), 1e-30)
            diff = np.abs(got - want)
            finite = np.isfinite(got).all()
            noise = max(ref.bf16_noise(keys), 1e-30)
            res["logit_gap"] = float(diff.max() / rms if finite else np.inf)
            res["logit_rms"] = float(np.sqrt(np.mean(np.square(diff))) / rms
                                     if finite else np.inf)
            res["logit_noise_ratio"] = float(
                np.sqrt(np.mean(np.square(diff))) / noise
                if finite else np.inf)
            cover["bf16_noise"] = noise
        else:
            res["logit_gap"] = res["logit_rms"] = float("inf")
            res["logit_noise_ratio"] = float("inf")
        cover["logits_compared"] = len(keys)
    return res, cover


def judge(limits: dict, res: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the numbers this cell
    reads that have a limit (a cell without capture reads no HP numbers).
    :func:`numbers` reads a number it cannot compute as infinite."""
    checks = {k: {"value": res[k], "limit": lim}
              for k, lim in limits.items() if k in res}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def run_check(sess) -> tuple[bool, dict, dict]:
    """The check of one run: reference vs the program's outputs. Returns
    ``(correct, checks, readings)``; readings hold every number read and
    what it covered."""
    out = program_outputs(sess)
    ref = Reference(sess, out.scores.shape[1])
    keys = logit_sample(sess, out) if sess.d is not None else []
    ref_out = ref.outputs(keys)
    res, cover = numbers(sess, out, ref, ref_out)
    ok, checks = judge(sess.cell.config["limits"], res)
    return ok, checks, {**res, **cover}


def control_outputs(sess, ref: Reference, logit_keys,
                    precision: str = "bfloat16") -> Outputs:
    """The control: the reference one precision below the stated one.

    Gate products on bfloat16 operands for the float32 datapath (``high``,
    three bfloat16 passes, is read too: see PERF.md); high-precision
    frames held in bfloat16 for float32; detector products on float8
    operands for bfloat16.
    """
    low = Reference(sess, ref.scores.shape[1], precision=precision)
    out = low.outputs(logit_keys, "float8")
    out.hp_frames = {k: np.asarray(jnp.asarray(v, jnp.bfloat16), np.float32)
                     for k, v in out.hp_frames.items()}
    return out
