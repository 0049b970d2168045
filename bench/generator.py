"""The one traffic generator: synthetic radar streams and their schedules.

Every traffic mix is a data file under ``bench/traffic/`` whose parameters
this module reads. Frames follow the event-track semantics of the
program's synthetic streams (Rayleigh speckle with a range ramp; objects
that appear in bursts of ``event_len`` frames with probability
``event_prob`` per idle frame and move on a linear track), rendered on the
device in one jitted call from the seed into a bounded pool that the run
replays. The gaps between events are the same for every seed, in another
order (:func:`event_gaps`). Replay advances the absolute frame index, so the service sees an
endless stream while the pool stays bounded.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: scene constants of the synthetic radar (CRUW-like 128x128 range-azimuth)
NOISE_SIGMA = 0.12
RANGE_RAMP = 0.08
SIDELOBE_GAIN = 0.15
V_MAX = 1.5
TRACK_SIGMA = 3.0
TRACK_AMP = 0.8
TRACK_MARGIN = 16
TRACK_CLIP = 6


def seed_words(seed: int, n: int, salt: int = 0) -> np.ndarray:
    """``n`` uint32 words from any non-negative integer seed and a salt."""
    return np.random.SeedSequence([int(seed), int(salt)]).generate_state(n)


def jax_key(seed: int, salt: int):
    """A PRNG key from a seed of any size (more than 32 bits included)."""
    return jax.random.PRNGKey(int(seed_words(seed, 1, salt)[0]))


def host_rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, 4, salt))


@functools.partial(jax.jit, static_argnames=("hw",))
def render(keys, active, cy, cx, sigma, amp, *, hw: tuple[int, int]):
    """``(N, H, W)`` float32 frames: speckle plus at most one blob each."""
    H, W = hw
    yy = jnp.arange(H, dtype=jnp.float32)[:, None]
    xx = jnp.arange(W, dtype=jnp.float32)[None, :]
    ramp = RANGE_RAMP * (1.0 - jnp.linspace(0.0, 1.0, H))[:, None]

    def one(k, on, y, x, s, a):
        k1, k2 = jax.random.split(k)
        re = jax.random.normal(k1, (H, W))
        im = jax.random.normal(k2, (H, W))
        bg = NOISE_SIGMA * jnp.sqrt(re * re + im * im) + ramp
        g = jnp.exp(-(((yy - y) / s) ** 2 + ((xx - x) / s) ** 2) / 2.0)
        streak = (jnp.exp(-(((yy - y) / s) ** 2) / 2.0) * SIDELOBE_GAIN
                  * jnp.exp(-jnp.abs(xx - x) / (6.0 * s)))
        return jnp.clip(bg + on * a * (g + streak), 0.0, V_MAX)

    return jax.vmap(one)(keys, active, cy, cx, sigma, amp)


def event_gaps(n_frames: int, event_prob: float, event_len: int) -> np.ndarray:
    """The idle gaps between the events of one stream, as a fixed multiset.

    An object appears with probability ``event_prob`` on each idle frame,
    so an idle gap is geometric. Rather than draw the gaps, every stream
    and every seed takes the same ones: the quantiles of that law at
    ``(k + 1/2) / K`` for as many events ``K`` as the stream holds on
    average. Only their order (and the tracks) change with the seed, so
    the seed never changes how much work a stream brings.
    """
    mean_gap = (1.0 - event_prob) / event_prob
    k = max(int(n_frames // (mean_gap + event_len)), 1)
    q = (np.arange(k) + 0.5) / k
    gaps = np.ceil(np.log1p(-q) / np.log1p(-event_prob)) - 1
    gaps = np.maximum(gaps, 0).astype(np.int64)
    while k > 1 and gaps.sum() + k * event_len > n_frames:
        k -= 1
        gaps = gaps[np.argsort(gaps)][:k]
    return gaps


def event_tracks(rng: np.random.Generator, n_frames: int, hw: tuple[int, int],
                 event_prob: float, event_len: int):
    """Per-frame ``(active, cy, cx)`` of one stream's tracked objects: the
    gaps of :func:`event_gaps` in an order drawn from ``rng``, each followed
    by an object on a linear track for ``event_len`` frames."""
    H, W = hw
    active = np.zeros(n_frames, np.float32)
    cy = np.zeros(n_frames, np.float32)
    cx = np.zeros(n_frames, np.float32)
    i = 0
    for gap in rng.permutation(event_gaps(n_frames, event_prob, event_len)):
        i += int(gap)
        length = min(event_len, n_frames - i)
        if length <= 0:
            break
        y0 = rng.uniform(TRACK_MARGIN, H - TRACK_MARGIN)
        x0 = rng.uniform(TRACK_MARGIN, W - TRACK_MARGIN)
        vy, vx = rng.uniform(-3, 3), rng.uniform(-3, 3)
        t = np.arange(length)
        active[i:i + length] = 1.0
        cy[i:i + length] = np.clip(y0 + vy * t, TRACK_CLIP, H - TRACK_CLIP)
        cx[i:i + length] = np.clip(x0 + vx * t, TRACK_CLIP, W - TRACK_CLIP)
        i += length
    return active, cy, cx


def radar_pool(seed: int, n_streams: int, n_frames: int,
               hw: tuple[int, int], event_prob: float, event_len: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """``(frames (n_streams, n_frames, H, W) float32, labels)`` on the host,
    rendered on the device in one call."""
    rng = host_rng(seed, 1)
    tracks = [event_tracks(rng, n_frames, hw, event_prob, event_len)
              for _ in range(n_streams)]
    active, cy, cx = (np.concatenate(t) for t in zip(*tracks))
    n = active.shape[0]
    keys = jax.random.split(jax_key(seed, 2), n)
    frames = render(keys, jnp.asarray(active), jnp.asarray(cy),
                    jnp.asarray(cx), jnp.full((n,), TRACK_SIGMA, jnp.float32),
                    jnp.full((n,), TRACK_AMP, jnp.float32), hw=tuple(hw))
    frames = np.asarray(frames).reshape(n_streams, n_frames, *hw)
    return frames, active.reshape(n_streams, n_frames) > 0


@dataclasses.dataclass(frozen=True)
class Traffic:
    """A traffic mix as its data file states it."""
    name: str
    loop: str                 # "closed": back to back; "open": on a clock
    sensors: int
    chunk: int
    max_inflight: int
    capture: bool             # closed-loop capture control with HP frames
    event_prob: float
    event_len: int
    pool_streams: int
    pool_frames: int
    frame_hz: float = 0.0     # open loop: the sensors' shared frame clock

    @property
    def period_s(self) -> float:
        """Seconds between two ticks of the open-loop clock."""
        return self.chunk / self.frame_hz

    def validate(self) -> None:
        if self.loop not in ("closed", "open"):
            raise ValueError(f"{self.name}: loop must be closed or open")
        if self.loop == "open" and self.frame_hz <= 0:
            raise ValueError(f"{self.name}: an open loop needs frame_hz")
        if self.pool_frames % self.chunk:
            raise ValueError(f"{self.name}: pool_frames must be a whole "
                             f"number of chunks")
        if min(self.sensors, self.chunk, self.max_inflight,
               self.pool_streams) < 1:
            raise ValueError(f"{self.name}: counts must be positive")


class Replay:
    """Which pool frame a sensor delivers at an absolute frame index.

    Sensor ``s`` replays pool stream ``s % pool_streams`` from an offset of
    ``(s // pool_streams) * chunk * 7`` frames, so sensors that share a
    stream are out of phase. Tick ``t`` delivers frames ``t*C .. t*C+C-1``.
    """

    def __init__(self, pool: np.ndarray, traffic: Traffic):
        self.pool = pool
        self.t = traffic

    def index(self, sensor: int, frame: np.ndarray) -> tuple[int, np.ndarray]:
        t = self.t
        off = (sensor // t.pool_streams) * t.chunk * 7
        return sensor % t.pool_streams, (np.asarray(frame) + off) % t.pool_frames

    def chunk(self, sensor: int, tick: int) -> np.ndarray:
        t = self.t
        stream, start = self.index(sensor, tick * t.chunk)
        return self.pool[stream, start:start + t.chunk]

    def arrivals(self, tick: int) -> dict:
        return {s: self.chunk(s, tick) for s in range(self.t.sensors)}


def open_schedule(t0: float, period_s: float, seconds: float) -> np.ndarray:
    """Due times of the ticks whose last frame arrives inside the window."""
    n = int(math.floor(seconds / period_s + 1e-9))
    return t0 + period_s * np.arange(1, n + 1)
