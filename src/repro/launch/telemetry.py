"""Always-on spans and counters of the served path.

An operator of :class:`~repro.launch.serve.FleetService` and
:class:`~repro.launch.cascade.CascadeService` reads here where the host's
time goes, tick by tick, without a profiler:

* :func:`span` times a block of host work on ``time.perf_counter_ns``.
  Each record keeps its name, start, end, the enclosing open span on the
  same thread (its parent) and a sequence number: the tick or batch it
  belongs to. A span given no ``seq`` takes its parent's, so all spans of
  one tick share it. The same block also opens a
  ``jax.profiler.TraceAnnotation``, so under a profiler session the spans
  land in the host plane of the trace, on the device events' clock; with
  no session running the annotation records nothing, and a whole span
  costs a few microseconds.
* :func:`count` adds to a named counter.
* :func:`snapshot` gives, per span, its count, total, median and longest
  duration with the ``seq`` of the longest, and every counter, as plain
  dicts. :func:`records` and :func:`durations` give a span's recent
  calls; :func:`reset` clears everything.

Recording is always on and bounded: per name, a ring of the last
:data:`RING` records, plus running totals over every call. Nothing here
waits for the device: a span times host work and the waits the served
path already makes.

The registry is the process's, keyed by name alone: it describes one
fleet service (and its cascade) per process. Two services in one process
record into the same spans and counters, and their ``seq`` numbers
collide.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Iterator

import jax
import numpy as np

#: records kept per span name (a 51 s window of 20 ticks/s is ~1,000)
RING = 4096


@dataclasses.dataclass(frozen=True)
class Span:
    """One finished span; times are ``perf_counter_ns``."""
    name: str
    start_ns: int
    end_ns: int
    parent: str | None
    seq: int | None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class _Totals:
    __slots__ = ("count", "total_ns", "max_ns", "max_seq", "ring")

    def __init__(self):
        self.count = self.total_ns = self.max_ns = 0
        self.max_seq = None
        self.ring: collections.deque[Span] = collections.deque(maxlen=RING)


class Registry:
    """Spans and counters of one process (see the module docstring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._open = threading.local()     # per thread: [(name, seq), ...]
        self._spans: dict[str, _Totals] = {}
        self._counters: dict[str, int] = collections.defaultdict(int)

    @contextlib.contextmanager
    def span(self, name: str, seq: int | None = None) -> Iterator[None]:
        stack = self._open.__dict__.setdefault("stack", [])
        parent, parent_seq = stack[-1] if stack else (None, None)
        seq = parent_seq if seq is None else seq
        stack.append((name, seq))
        ann = (jax.profiler.TraceAnnotation(name) if seq is None
               else jax.profiler.TraceAnnotation(name, seq=seq))
        t0 = time.perf_counter_ns()
        try:
            with ann:
                yield
        finally:
            t1 = time.perf_counter_ns()
            stack.pop()
            self._record(Span(name, t0, t1, parent, seq))

    def _record(self, rec: Span) -> None:
        dur = rec.end_ns - rec.start_ns
        with self._lock:
            tot = self._spans.get(rec.name)
            if tot is None:
                tot = self._spans[rec.name] = _Totals()
            tot.count += 1
            tot.total_ns += dur
            if dur > tot.max_ns:
                tot.max_ns, tot.max_seq = dur, rec.seq
            tot.ring.append(rec)

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] += int(n)

    def records(self, name: str) -> list[Span]:
        """The span's last :data:`RING` records, oldest first."""
        with self._lock:
            tot = self._spans.get(name)
            return list(tot.ring) if tot is not None else []

    def durations(self, name: str) -> list[float]:
        """Seconds of the span's last :data:`RING` calls, oldest first."""
        return [r.seconds for r in self.records(name)]

    def snapshot(self) -> dict:
        """``{"spans": {name: {count, total_s, max_s, max_seq, p50_s}},
        "counters": {name: value}}``; ``p50_s`` is over the ring."""
        with self._lock:
            spans = {
                name: {"count": t.count, "total_s": t.total_ns * 1e-9,
                       "max_s": t.max_ns * 1e-9, "max_seq": t.max_seq,
                       "p50_s": float(np.median(
                           [r.end_ns - r.start_ns for r in t.ring])) * 1e-9}
                for name, t in self._spans.items()}
            return {"spans": spans, "counters": dict(self._counters)}

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._counters.clear()


#: the process's registry, which the served path records into
REGISTRY = Registry()
span = REGISTRY.span
count = REGISTRY.count
records = REGISTRY.records
durations = REGISTRY.durations
snapshot = REGISTRY.snapshot
reset = REGISTRY.reset
