"""Named host spans of the program: on the profiler's clock and in a ring.

    with span("engine.decode", active=32, ctx=16400) as counts:
        ...
        counts["finished"] = 3      # a count known only at the end

Each span does two things:

- while a profile is running, it opens a `jax.profiler.TraceAnnotation`
  of the same name and counts, so it shows in the trace on the clock of
  the device planes, whoever started the profile (a benchmark, or an
  operator with xprof or TensorBoard);
- it appends a `Span` record, stamped with `time.perf_counter_ns()`, to a
  bounded ring: an always-on flight recorder that `records(t0, t1)` reads.
  Like the profiler's own trace, the ring is one per process.

Counts are integers attached to the span at the boundary where the work
happens. Names are dotted by layer (`actor.*`, `engine.*`, `trainer.*`);
a span's `parent` is the name of the span open around it on the same
thread, or None at the top.

The ring holds the newest `CAPACITY` records: about three minutes of
the one-chip RL benchmark cell, which opens about 375 spans a second on a
TPU v5e. A record is one flat tuple of strings and integers, which the
garbage collector stops tracking at its first pass, so the ring adds
nothing to the collector's full passes. With four counts a record takes
at most `RECORD_BYTES` (its strings are shared), so the ring stays under
`CAPACITY * RECORD_BYTES`, 24 MiB.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

CAPACITY = 1 << 16
RECORD_BYTES = 384


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]
    counts: Dict[str, int]


# (name, start_ns, end_ns, parent, key, value, key, value, ...)
_ring: "collections.deque[tuple]" = collections.deque(maxlen=CAPACITY)
_open = threading.local()
_Annotation = jax.profiler.TraceAnnotation


class span:
    """Context manager: one named span; `as` gives its mutable counts."""

    __slots__ = ("name", "counts", "_ann", "_parent", "_start", "_n0")

    def __init__(self, name: str, **counts: int):
        self.name, self.counts = name, counts

    def __enter__(self) -> Dict[str, int]:
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        # TraceMe records nothing unless a profile is running at entry
        self._ann = None
        if _Annotation.is_enabled():
            self._n0 = len(self.counts)
            self._ann = _Annotation(self.name, **self.counts)
            self._ann.__enter__()
        self._start = time.perf_counter_ns()
        return self.counts

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        if self._ann is not None:
            if len(self.counts) != self._n0:
                self._ann.set_metadata(**self.counts)
            self._ann.__exit__(*exc)
        _open.stack.pop()
        _ring.append((self.name, self._start, end, self._parent)
                     + sum(self.counts.items(), ()))


def records(t0: float, t1: float) -> List[Span]:
    """Spans that start in [t0, t1) on the `time.perf_counter` clock
    (seconds), ordered by start."""
    lo, hi = int(t0 * 1e9), int(t1 * 1e9)
    return [Span(*r[:4], dict(zip(r[4::2], r[5::2])))
            for r in sorted(list(_ring), key=lambda r: r[1])
            if lo <= r[1] < hi]


def named(fn, name: str):
    """Give a callable (a `functools.partial`, say) the name its jitted
    program compiles under: `jit_<name>` in HLO and in profiler traces."""
    fn.__name__ = fn.__qualname__ = name
    return fn
