"""Spans: where the program's wall time goes, on the profiler's clock.

    with obs.span("snapshot", step=s):
        with obs.span("snapshot.copy", bytes=n):
            ...

A span records its name, start and end (``time.perf_counter`` seconds),
the span it ran inside, the training step it belongs to (``step``: given,
else the enclosing span's, else 0) and a few small integer attributes.
Each span is also a ``jax.profiler.TraceAnnotation`` of the same name, so
a profile shows the program's spans beside the device's operations.

Records go to a bounded ring (``CAPACITY``; the oldest are dropped and
counted) and into per-name totals: count, total and max seconds.  Reading
is ``records()``, ``totals()`` and ``reset()``; nothing is written to
disk.  A span costs two clock reads and one append, plus the annotation,
which is close to free while no profiler runs.  Spans never wait for the
device: a span that times device work ends on a ``block_until_ready`` of
its caller's.

Clock: the profiler stamps its events on the wall clock
(``time.time_ns``) and reports them relative to the trace's start (the
``profile_start_time`` of the trace's "Task Environment" plane).
``to_trace_ns`` maps a span's ``perf_counter`` time onto that scale through
one offset between the two clocks, taken when this module is imported.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional

import jax

#: records kept in the ring; older ones are dropped and counted
CAPACITY = 4096


def _wall_minus_perf_ns() -> int:
    """``time.time_ns() - time.perf_counter_ns()``, from the tightest of a
    few bracketed readings."""
    best = None
    for _ in range(16):
        a = time.perf_counter_ns()
        w = time.time_ns()
        b = time.perf_counter_ns()
        if best is None or b - a < best[0]:
            best = (b - a, w - (a + b) // 2)
    return best[1]


#: fixed offset from ``perf_counter`` to the profiler's wall clock (ns)
WALL_MINUS_PERF_NS = _wall_minus_perf_ns()


def to_trace_ns(t: float, profile_start_ns: int) -> float:
    """A ``perf_counter`` time as the profiler's ns since the start of a
    trace that began at ``profile_start_ns`` (wall-clock ns)."""
    return t * 1e9 + WALL_MINUS_PERF_NS - profile_start_ns


class Tally:
    """Count, total and max seconds of each span name."""

    def __init__(self):
        self.by_name: Dict[str, List[float]] = {}

    def add(self, name: str, seconds: float) -> None:
        t = self.by_name.get(name)
        if t is None:
            self.by_name[name] = [1, seconds, seconds]
        else:
            t[0] += 1
            t[1] += seconds
            if seconds > t[2]:
                t[2] = seconds

    def summary(self) -> Dict[str, Dict]:
        return {name: {"count": int(c), "total_s": tot, "max_s": mx}
                for name, (c, tot, mx) in sorted(self.by_name.items())}


class Records(list):
    """The ring's records, oldest first, with the count dropped."""
    dropped = 0


class Span:
    """One timed region; the record it leaves is the object itself."""

    __slots__ = ("id", "name", "step", "attrs", "parent", "start", "end",
                 "_ann")

    def __init__(self, name: str, step: Optional[int], attrs: Dict):
        self.name = name
        self.step = step
        self.attrs = attrs
        self.id = self.parent = None
        self.start = self.end = 0.0

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        stack = _stack()
        if stack:
            top = stack[-1]
            self.parent = top.id
            if self.step is None:
                self.step = top.step
        elif self.step is None:
            self.step = 0
        self.id = next(_IDS)
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name, step=self.step)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        self._ann = None
        _stack().pop()
        _RECORDER.record(self)
        return False


class _Recorder:
    def __init__(self):
        self.lock = threading.Lock()
        self.ring: deque = deque(maxlen=CAPACITY)
        self.dropped = 0
        self.totals = Tally()
        self.tallies: List[Tally] = []

    def record(self, sp: Span) -> None:
        secs = sp.end - sp.start
        with self.lock:
            if len(self.ring) == self.ring.maxlen:
                self.dropped += 1
            self.ring.append(sp)
            self.totals.add(sp.name, secs)
            for t in self.tallies:
                t.add(sp.name, secs)


_RECORDER = _Recorder()
_LOCAL = threading.local()
#: span ids, in order of opening (``next`` is atomic under the GIL)
_IDS = itertools.count(1)


def _stack() -> List[Span]:
    s = getattr(_LOCAL, "stack", None)
    if s is None:
        s = _LOCAL.stack = []
    return s


def span(name: str, *, step: Optional[int] = None, **attrs: int) -> Span:
    """A span to open with ``with``; ``attrs`` are small ints (``bytes``,
    ``steps``), and may be added to ``.attrs`` before it closes."""
    return Span(name, step, attrs)


@contextmanager
def tally() -> Iterator[Tally]:
    """Totals of the spans that close while the block runs (what one
    ``train()`` call reports under ``"spans"``)."""
    t = Tally()
    with _RECORDER.lock:
        _RECORDER.tallies.append(t)
    try:
        yield t
    finally:
        with _RECORDER.lock:
            _RECORDER.tallies.remove(t)


def records() -> Records:
    """The ring's spans, oldest first; ``.dropped`` counts those lost."""
    with _RECORDER.lock:
        out = Records(_RECORDER.ring)
        out.dropped = _RECORDER.dropped
    return out


def totals() -> Dict[str, Dict]:
    """Count, total and max seconds of every span name since ``reset``."""
    with _RECORDER.lock:
        return _RECORDER.totals.summary()


def reset() -> None:
    """Empty the ring and the totals."""
    with _RECORDER.lock:
        _RECORDER.ring.clear()
        _RECORDER.dropped = 0
        _RECORDER.totals = Tally()
