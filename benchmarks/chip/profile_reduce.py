"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

`load` turns an `.xplane.pb` into plain interval lists: the device
operations of every accelerator plane, the device's program (module)
executions, and the host spans the benchmark opened with `SPAN_PREFIX`.
Everything after that is arithmetic on intervals, checked by the tests on
synthetic traces:

- busy time: the union of a device's operation intervals;
- program roles: each program execution belongs to the benchmark span
  (decode, admit, train_step, install) that launched it, and a kernel
  call to the program it runs in;
- device time by name, and self time (nested operations subtracted);
- idle gaps: the holes in the union, each labelled by the innermost
  benchmark span open over its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench:"

Interval = Tuple[float, float]          # (start_s, end_s)


@dataclasses.dataclass
class Op:
    name: str           # as the trace shows it (HLO text for operations)
    start: float        # seconds
    end: float


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Op]]            # device plane -> operations
    modules: Dict[str, List[Op]]        # device plane -> program executions
    spans: List[Op]                     # benchmark host spans
    span: Interval                      # the traced window

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _op(ev) -> Op:
    start = ev.start_ns * 1e-9
    return Op(ev.name, start, start + ev.duration_ns * 1e-9)


def load(path: str, span: Interval) -> Trace:
    """Read the trace file. Device planes are those named `/device:*`
    (not the host's); their "XLA Modules" line holds program executions
    and their "XLA Ops" line the operations."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Op]] = {}
    modules: Dict[str, List[Op]] = {}
    spans: List[Op] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(
                        _op(e) for e in line.events)
                elif line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(
                        _op(e) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(_op(e))
    return Trace(ops, modules, spans, span)


def clip(ivs: Iterable[Interval], span: Interval) -> List[Interval]:
    lo, hi = span
    return [(max(a, lo), min(b, hi)) for a, b in ivs if b > lo and a < hi]


def union(ivs: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(ivs):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(ops: Sequence[Op], span: Interval) -> float:
    return sum(b - a for a, b in union(clip(((o.start, o.end) for o in ops),
                                            span)))


def gaps(ops: Sequence[Op], span: Interval) -> List[Interval]:
    """Idle intervals of the span: where no operation runs."""
    out, cur = [], span[0]
    for a, b in union(clip(((o.start, o.end) for o in ops), span)):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < span[1]:
        out.append((cur, span[1]))
    return out


def matching(ops: Sequence[Op], needles: Sequence[str]) -> List[Op]:
    return [o for o in ops if any(n in o.name for n in needles)]


def summed(ops: Sequence[Op], span: Optional[Interval] = None) -> float:
    if span is None:
        return sum(o.end - o.start for o in ops)
    return sum(b - a for a, b in clip(((o.start, o.end) for o in ops), span))


def label_at(spans: Sequence[Op], t: float, default: str = "untraced") -> str:
    """Innermost benchmark span open at time t (the shortest one)."""
    best = None
    for s in spans:
        if s.start <= t < s.end and (best is None
                                     or s.end - s.start < best.end - best.start):
            best = s
    return best.name[len(SPAN_PREFIX):] if best is not None else default


def self_times(ops: Sequence[Op]) -> List[float]:
    """Each operation's duration less the time of operations nested inside
    it (a `while` op spans the ops of its body on the same line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    own = [ops[i].end - ops[i].start for i in range(len(ops))]
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]].end <= ops[i].start:
            stack.pop()
        if stack and ops[i].end <= ops[stack[-1]].end:
            own[stack[-1]] -= ops[i].end - ops[i].start
        stack.append(i)
    return own


def op_label(name: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12 = bf16[...]`."""
    head = name.lstrip("%")
    cut = head.find(" = ")
    if cut < 0:
        return head[:120]
    rhs = head[cut + 3:]
    return head[:cut] + " = " + rhs.split(" ")[0][:100]


def top_ops(ops: Sequence[Op], n: int = 10) -> List[List]:
    """Device operations that took most time (self time, nested operations
    subtracted), by operation."""
    tot: Dict[str, float] = {}
    for o, t in zip(ops, self_times(ops)):
        k = op_label(o.name)
        tot[k] = tot.get(k, 0.0) + t
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


ROLES = ("decode", "admit", "train_step", "install")


def module_roles(tr: "Trace") -> Dict[str, List[Op]]:
    """Program executions by the role of the program: each execution gets
    the role span (decode, admit, train_step, install) that started last
    at or before it, and every execution of one program takes the role
    most of its executions got (host and device clocks can disagree by a
    little). Programs are jitted partials, so their names in the trace
    (`jit__unknown(<hash>)`) carry no function name to match."""
    role = sorted((s.start, s.name[len(SPAN_PREFIX):]) for s in tr.spans
                  if s.name[len(SPAN_PREFIX):] in ROLES)
    starts = [a for a, _ in role]
    votes: Dict[str, Dict[str, int]] = {}
    for mods in tr.modules.values():
        for m in mods:
            i = bisect.bisect_right(starts, m.start) - 1
            if i >= 0:
                v = votes.setdefault(m.name, {})
                v[role[i][1]] = v.get(role[i][1], 0) + 1
    winner = {k: max(v, key=v.get) for k, v in votes.items()}
    out: Dict[str, List[Op]] = {r: [] for r in ROLES}
    for mods in tr.modules.values():
        for m in mods:
            if m.name in winner:
                out[winner[m.name]].append(m)
    return out


def role_seconds_by_plane(tr: "Trace", role: str) -> List[float]:
    """Device seconds of a role's program executions in the window, on
    each device plane that ran one."""
    mine = {id(m) for m in module_roles(tr)[role]}
    out = []
    for mods in tr.modules.values():
        t = summed([m for m in mods if id(m) in mine], tr.span)
        if t > 0:
            out.append(t)
    return out


def ops_within(ops: Sequence[Op], within: Sequence[Op]) -> List[Op]:
    """Operations that start inside one of the `within` intervals."""
    iv = sorted((w.start, w.end) for w in within)
    starts = [a for a, _ in iv]
    out = []
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.start < iv[i][1]:
            out.append(o)
    return out


def kernels(tr: "Trace", role: str) -> List[Op]:
    """Pallas kernel calls inside the programs of a role: the custom calls
    that carry a name of their own (XLA's own custom calls are named
    `custom-call.<n>`)."""
    mods = module_roles(tr)[role]
    return [o for k in tr.devices for o in ops_within(tr.ops[k], mods)
            if "custom-call(" in o.name
            and not o.name.lstrip("%").startswith("custom-call")]


def span_count(tr: "Trace", role: str) -> int:
    return sum(1 for s in tr.spans if s.name == SPAN_PREFIX + role
               and tr.span[0] <= s.start < tr.span[1])


def top_gaps(ops: Sequence[Op], spans: Sequence[Op], span: Interval,
             n: int = 10) -> List[List]:
    """The longest idle gaps, each named by what the host was doing."""
    gs = sorted(gaps(ops, span), key=lambda g: g[0] - g[1])[:n]
    return [[label_at(spans, 0.5 * (a + b)), b - a] for a, b in gs]
