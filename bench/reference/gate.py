"""Plain reference of the HyperSense gate, and the gate's weights.

Everything here is written from the paper's description (HyperSense,
arXiv:2401.10267, Sec. III) in straightforward ``jax.numpy`` and numpy,
and imports nothing of the program:

- the ADC: uniform quantization over ``[0, 1.5]`` to ``bits`` bits;
- fragment encoding: every ``h x w`` sliding window is cropped,
  flattened, L2-normalized and projected on the permutation-structured
  base ``B[r, j] = roll(B0[r], -j)``, then ``phi = cos(p + b) * sin(p)``;
- the frame score: cosine similarity to the positive minus the negative
  class hypervector, per fragment; the frame's score is the
  ``(t_detection + 1)``-th largest;
- the gate: a frame fires when its score exceeds ``t_score``; the
  closed-loop controller samples one idle frame per ``decim``, and holds
  the burst (every frame sampled and captured) for ``hold`` frames.

The gate's weights are made here from the seed, on the device: the base
and phase are random, the class hypervectors are trained on labelled
fragments of synthetic radar frames (bundling, then similarity-scaled
perceptron epochs, keeping the best epoch), and the threshold is set on
the traffic's own stream pool, scored by this reference: at a
false-positive rate on its empty frames (:func:`with_threshold`), or where
the closed-loop controller captures a given share of it (:func:`with_duty`).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import generator

V_MAX = 1.5


def quantize(x, bits: int):
    """ADC reconstruction ``code * LSB`` (jnp, float32)."""
    levels = (1 << bits) - 1
    codes = jnp.round(jnp.clip(x, 0.0, V_MAX) / V_MAX * levels)
    return codes * jnp.float32(V_MAX / levels)


def quantize_hp(x: np.ndarray, bits: int) -> np.ndarray:
    """High-precision capture of raw frames (numpy, float32 throughout)."""
    levels = np.float32((1 << bits) - 1)
    x = np.asarray(x, np.float32)
    codes = np.round(np.clip(x, np.float32(0), np.float32(V_MAX))
                     / np.float32(V_MAX) * levels)
    return codes * (np.float32(V_MAX) / levels)


def flat_base(B0, w: int):
    """``(h*w, D)``: row ``r*w + j`` is ``B0[r]`` rolled left by ``j``."""
    h, dim = B0.shape
    idx = (jnp.arange(dim)[None, :] + jnp.arange(w)[:, None]) % dim  # (w, D)
    return B0[:, idx].reshape(h * w, dim)


def fragments(frames, h: int, w: int, stride: int):
    """``(N, H, W)`` -> ``(N, my*mx, h*w)`` sliding-window crops."""
    N, H, W = frames.shape
    my = (H - h) // stride + 1
    mx = (W - w) // stride + 1
    ys = jnp.arange(my) * stride
    xs = jnp.arange(mx) * stride
    rows = frames[:, ys[:, None] + jnp.arange(h)[None, :], :]     # N,my,h,W
    crop = rows[:, :, :, xs[:, None] + jnp.arange(w)[None, :]]    # N,my,h,mx,w
    crop = jnp.transpose(crop, (0, 1, 3, 2, 4))                   # N,my,mx,h,w
    return crop.reshape(N, my * mx, h * w)


def matmul(a, b, precision: str):
    """``a @ b`` with float32 accumulation, its operands at ``precision``:
    ``"highest"`` (float32), ``"high"`` (three bfloat16 passes: hi*hi +
    hi*lo + lo*hi) or ``"bfloat16"`` (one pass). The lower two are spelt
    out, so that each means the same on every backend."""
    bf, f32 = jnp.bfloat16, jnp.float32
    dot = functools.partial(jnp.matmul, preferred_element_type=f32)
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "bfloat16":
        return dot(a.astype(bf), b.astype(bf))
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    # reduce_precision, not a round trip through bfloat16, which XLA may
    # fold away and so leave the low parts zero
    hi = functools.partial(jax.lax.reduce_precision, exponent_bits=8,
                           mantissa_bits=7)
    a_hi, b_hi = hi(a), hi(b)
    a_lo, b_lo = (a - a_hi).astype(bf), (b - b_hi).astype(bf)
    a_hi, b_hi = a_hi.astype(bf), b_hi.astype(bf)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def encode(x, Bf, b, precision: str):
    """Normalized flat fragments ``(..., n)`` -> ``phi (..., D)``."""
    x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-8)
    p = matmul(x, Bf, precision)
    return jnp.cos(p + b) * jnp.sin(p)


def cosine_margin(phi, class_hvs, precision: str):
    """``sim(phi, C1) - sim(phi, C0)`` along the last axis."""
    q = phi / jnp.maximum(jnp.linalg.norm(phi, axis=-1, keepdims=True), 1e-9)
    c = class_hvs / jnp.maximum(
        jnp.linalg.norm(class_hvs, axis=-1, keepdims=True), 1e-9)
    s = matmul(q, c.T, precision)
    return s[..., 1] - s[..., 0]


@functools.partial(jax.jit, static_argnames=("h", "w", "stride", "bits",
                                             "t_detection", "precision"))
def frame_scores(frames, B0, b, class_hvs, *, h: int, w: int, stride: int,
                 bits: int, t_detection: int, precision: str):
    """Raw ``(N, H, W)`` frames -> ``(N,)`` frame scores, through the ADC."""
    Bf = flat_base(B0, w)
    lp = quantize(frames, bits)
    x = fragments(lp, h, w, stride)
    s = cosine_margin(encode(x, Bf, b, precision), class_hvs,
                      precision)                               # (N, frags)
    k = min(t_detection, s.shape[-1] - 1)
    return -jnp.sort(-s, axis=-1)[:, k]


def score_frames(frames: np.ndarray, weights: "GateWeights", g: dict, *,
                 precision: str = "highest", block: int = 32) -> np.ndarray:
    """Frame scores of a host array of raw frames, ``block`` at a time.

    The last block is padded to ``block`` frames, so one shape compiles.
    """
    frames = np.asarray(frames, np.float32).reshape(-1, g["frame_h"],
                                                    g["frame_w"])
    n = frames.shape[0]
    out = np.empty(n, np.float32)
    for lo in range(0, n, block):
        part = frames[lo:lo + block]
        m = part.shape[0]
        if m < block:
            part = np.concatenate(
                [part, np.zeros((block - m, *part.shape[1:]), np.float32)])
        s = frame_scores(jnp.asarray(part), weights.B0, weights.b,
                         weights.class_hvs, h=g["fragment"], w=g["fragment"],
                         stride=g["stride"], bits=g["adc_low_bits"],
                         t_detection=g["t_detection"], precision=precision)
        out[lo:lo + m] = np.asarray(s)[:m]
    return out


def control_scan(scores: np.ndarray, t_score: float, hold_frames: int,
                 decim: int | None, hold: int = 0, phase: int = 0):
    """One sensor's decisions over consecutive frames.

    Returns ``(fired, gated, sampled, hold, phase)``; ``decim=None`` is the
    open loop (every frame sampled).
    """
    n = scores.shape[0]
    fired = np.zeros(n, bool)
    gated = np.zeros(n, bool)
    sampled = np.ones(n, bool)
    above = scores > np.float32(t_score)
    for i in range(n):
        smp = decim is None or phase == 0 or hold > 0
        f = bool(above[i]) and smp
        gated[i] = f or hold > 0
        fired[i] = f
        sampled[i] = smp
        hold = hold_frames if f else max(hold - 1, 0)
        if decim is not None:
            phase = decim - 1 if smp else phase - 1
    return fired, gated, sampled, hold, phase


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GateWeights:
    B0: jax.Array          # (h, D) permutation generators
    b: jax.Array           # (D,) phase
    class_hvs: jax.Array   # (2, D): 0 = empty, 1 = object
    t_score: float


def _labelled_frames(seed: int, salt: int, n: int, g: dict):
    """``n`` raw frames, the first half with one object, and the object
    centres (host rng), rendered on the device."""
    rng = generator.host_rng(seed, salt)
    H, W = g["frame_h"], g["frame_w"]
    labels = np.arange(n) < n // 2
    cy = rng.uniform(8, H - 8, n).astype(np.float32)
    cx = rng.uniform(8, W - 8, n).astype(np.float32)
    sig = rng.uniform(2.0, 6.0, n).astype(np.float32)
    amp = rng.uniform(0.45, 1.0, n).astype(np.float32)
    keys = jax.random.split(generator.jax_key(seed, salt), n)
    frames = generator.render(keys, jnp.asarray(labels, jnp.float32),
                              jnp.asarray(cy), jnp.asarray(cx),
                              jnp.asarray(sig), jnp.asarray(amp), hw=(H, W))
    return frames, labels, cy, cx, rng


@functools.partial(jax.jit, static_argnames=("h", "w", "epochs"))
def _train(frags, labels, B0, b, *, h: int, w: int, epochs: int):
    """Bundle, then ``epochs`` perceptron epochs; the most accurate epoch."""
    phi = encode(frags, flat_base(B0, w), b, "highest")            # (n, D)
    onehot = jax.nn.one_hot(labels, 2, dtype=jnp.float32)
    chv = matmul(onehot.T, phi, "highest")

    def sims(c, v):
        vn = v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-9)
        cn = c / jnp.maximum(jnp.linalg.norm(c, axis=-1, keepdims=True), 1e-9)
        return matmul(vn, cn.T, "highest")

    def step(c, xy):
        v, y = xy
        s = sims(c, v[None])[0]
        pred = jnp.argmax(s)
        rate = 1.0 - s[y]
        wrong = (pred != y).astype(jnp.float32)
        upd = jnp.zeros_like(c).at[y].add(rate * v).at[pred].add(-rate * v)
        return c + wrong * upd, None

    def epoch(carry, _):
        c, best, best_acc = carry
        c, _ = jax.lax.scan(step, c, (phi, labels))
        acc = jnp.mean(jnp.argmax(sims(c, phi), axis=-1) == labels)
        better = acc > best_acc
        return (c, jnp.where(better, c, best), jnp.maximum(acc, best_acc)), None

    (_, best, _), _ = jax.lax.scan(epoch, (chv, chv, jnp.float32(-1.0)),
                                   None, length=epochs)
    return best


def make_weights(seed: int, g: dict, tr: dict) -> GateWeights:
    """Train the gate from ``seed`` at ``g``'s geometry (see module doc)."""
    h, w, D = g["fragment"], g["fragment"], g["dim"]
    H, W = g["frame_h"], g["frame_w"]
    kb, kp = jax.random.split(generator.jax_key(seed, 10))
    B0 = jax.random.normal(kb, (h, D), jnp.float32)
    b = jax.random.uniform(kp, (D,), jnp.float32, 0.0, 2 * math.pi)

    n = tr["frames"]
    frames, labels, cy, cx, rng = _labelled_frames(seed, 11, n, g)
    lp = quantize(frames, g["adc_low_bits"])
    # per frame: windows containing the object (object frames) or any
    # window (empty frames), so the fragment set stays balanced
    k = tr["fragments_per_frame"]
    fi = np.repeat(np.arange(n), k)
    y0 = np.where(labels[fi], cy[fi] - rng.integers(0, h, fi.size),
                  rng.integers(0, H - h + 1, fi.size))
    x0 = np.where(labels[fi], cx[fi] - rng.integers(0, w, fi.size),
                  rng.integers(0, W - w + 1, fi.size))
    y0 = np.clip(np.floor(y0), 0, H - h).astype(np.int32)
    x0 = np.clip(np.floor(x0), 0, W - w).astype(np.int32)
    crop = jax.vmap(lambda f, y, x: jax.lax.dynamic_slice(f, (y, x), (h, w)))
    frags = crop(lp[jnp.asarray(fi)], jnp.asarray(y0), jnp.asarray(x0))
    chv = _train(frags.reshape(fi.size, h * w),
                 jnp.asarray(labels[fi], jnp.int32), B0, b, h=h, w=w,
                 epochs=tr["epochs"])
    return GateWeights(B0, b, chv, 0.0)


def with_threshold(weights: GateWeights, empty_scores: np.ndarray,
                   fpr: float) -> GateWeights:
    """``weights`` with ``t_score`` set so that a share ``fpr`` of the
    empty frames' scores ``empty_scores`` lies above it."""
    neg = np.sort(np.asarray(empty_scores).ravel())[::-1]
    t = float(neg[min(int(math.floor(fpr * neg.size)), neg.size - 1)])
    return dataclasses.replace(weights, t_score=t)


def captured_share(stream_scores: np.ndarray, t_score: float,
                   hold_frames: int, decim: int) -> float:
    """Share of frames the closed-loop controller captures at high
    precision when each stream of ``(streams, frames)`` scores is replayed
    without end: the second of two passes, once the first has set the
    hold and the phase."""
    n = stream_scores.shape[1]
    gated = [control_scan(np.concatenate([s, s]), t_score, hold_frames,
                          decim)[1][n:] for s in stream_scores]
    return float(np.mean(gated))


def with_duty(weights: GateWeights, stream_scores: np.ndarray,
              hold_frames: int, decim: int, duty: float,
              candidates: int = 256) -> GateWeights:
    """``weights`` with the ``t_score``, among ``candidates`` quantiles of
    the pool's scores, whose :func:`captured_share` lies nearest ``duty``:
    every seed's gate then sends about the same share of frames to the
    detector, however well it learned."""
    cand = np.quantile(stream_scores, np.linspace(0.0, 1.0, candidates))
    share = [captured_share(stream_scores, float(t), hold_frames, decim)
             for t in cand]
    best = int(np.argmin(np.abs(np.asarray(share) - duty)))
    return dataclasses.replace(weights, t_score=float(cand[best]))
