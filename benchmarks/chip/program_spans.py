"""The program's own spans (`repro.spans`), placed on the trace's clock.

The program keeps its host spans in a ring on the `time.perf_counter`
clock. A reader takes the window's spans from it and moves them onto the
trace's clock with one offset: the benchmark's window span opens a few
microseconds before the harness reads `ctx.window["t0"]`, so
`ctx.trace.span[0] - ctx.window["t0"]` maps one clock onto the other.

Each idle instant of a device (a hole in the union of its operations)
goes to the innermost program span open over it (the one that started
last), or to none: the event loop between calls. A program older than
`repro.spans` gives no records, and the readers then return None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import profile_reduce as PR

Placed = Tuple[float, float, str]     # (start_s, end_s, name) on the trace


def window_spans(ctx) -> Optional[list]:
    """The program's spans that start in the window, or None."""
    try:
        from repro import spans
    except ImportError:
        return None
    w = ctx.window
    return spans.records(w["t0"], w["t1"]) or None


def place(ctx, recs) -> List[Placed]:
    off = ctx.trace.span[0] - ctx.window["t0"]
    return [(r.start_ns * 1e-9 + off, r.end_ns * 1e-9 + off, r.name)
            for r in recs]


def innermost(placed: Sequence[Placed], span: PR.Interval
              ) -> List[Tuple[float, float, Optional[str]]]:
    """Cut the window into pieces, each named by the innermost span open
    over it (None where no span is open)."""
    lo, hi = span
    # closes before opens at one instant; of two opening together the
    # longer first, so the shorter is the innermost
    marks = sorted([(a, 1, a - b, i) for i, (a, b, _) in enumerate(placed)]
                   + [(b, 0, 0.0, i) for i, (a, b, _) in enumerate(placed)])
    out: List[Tuple[float, float, Optional[str]]] = []
    active: List[int] = []            # open spans, by start
    cur = lo
    for t, is_open, _, i in marks:
        t = min(max(t, lo), hi)
        if t > cur:
            out.append((cur, t, placed[active[-1]][2] if active else None))
            cur = t
        if is_open:
            active.append(i)
        else:
            active.remove(i)
    if cur < hi:
        out.append((cur, hi, None))
    return out


def idle_by_name(ops: Sequence[PR.Op], placed: Sequence[Placed],
                 span: PR.Interval) -> Dict[Optional[str], float]:
    """Idle seconds of one device in the window, by innermost span."""
    idle = PR.gaps(ops, span)
    out: Dict[Optional[str], float] = {}
    j = 0
    for a, b, name in innermost(placed, span):
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < b:
            d = min(b, idle[k][1]) - max(a, idle[k][0])
            if d > 0:
                out[name] = out.get(name, 0.0) + d
            k += 1
    return out


def idle_seconds(ctx, recs) -> Optional[Dict[Optional[str], float]]:
    """Idle seconds by innermost span, averaged over the devices used."""
    tr = ctx.trace
    used = [k for k in tr.devices if tr.ops[k]] if tr is not None else []
    if not used:
        return None
    placed = place(ctx, recs)
    out: Dict[Optional[str], float] = {}
    for k in used:
        for name, s in idle_by_name(tr.ops[k], placed, tr.span).items():
            out[name] = out.get(name, 0.0) + s / len(used)
    return out


def gap_ms(ctx, prefixes: Sequence[Optional[str]], per: str
           ) -> Optional[float]:
    """Device idle milliseconds under spans whose names start with one of
    `prefixes` (None: under no span), per span named `per`."""
    recs = window_spans(ctx)
    if recs is None:
        return None
    n = sum(1 for r in recs if r.name == per)
    idle = idle_seconds(ctx, recs)
    if idle is None or n == 0:
        return None
    s = sum(v for name, v in idle.items()
            if any(name is None if p is None
                   else name is not None and name.startswith(p)
                   for p in prefixes))
    return 1e3 * s / n
