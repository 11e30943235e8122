"""The trace reduction as a sweep over sorted intervals, with the program's
own spans beside the benchmark's.

`trace.reduce_events` finds each span's device busy time by walking every
busy interval, and labels each idle gap by walking every span: its cost
grows as spans x intervals and gaps x spans, minutes for a window of a
few thousand calls. `reduce_events` here gives the same `trace.Reduced`
fields from sorted intervals: busy time inside a span from prefix sums
found by bisection, and the spans over each gap from an active set swept
along the gaps in order. The benchmark's spans (`SPANS` of a driver) and
the window alone decide every field `trace.reduce_events` gives, the cut
included, so those read the same to the bit. It adds what the program's
spans (`jax.profiler.TraceAnnotation`s under `src/repro`) hold:

* `span_host_s`: host seconds per benchmark span name;
* `prog_host_s`, `prog_count`: host seconds and count per program span
  name, and `prog_stats`: per name, the sum of each numeric stat. A
  program span counts when it ends inside the kept window and lies inside
  a kept benchmark span, so a ratio of the two divides by the same calls
  on both sides, also where the device trace is cut;
* idle time by program span: each instant of an idle gap goes to the
  innermost program span over it. `prog_idle_s` sums that per name
  (averaged over devices, as `busy_s` is), and each gap in `idle_gaps`
  takes the name that holds most of it; where no program span covers any
  of it, the benchmark span that `trace.reduce_events` names.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, Iterable, List, Sequence, Tuple

from chipbench import trace

Span = Tuple[str, int, int]
ProgramSpan = Tuple[str, int, int, Dict[str, float]]


@dataclasses.dataclass
class Reduced(trace.Reduced):
    span_host_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    prog_host_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    prog_count: Dict[str, int] = dataclasses.field(default_factory=dict)
    prog_stats: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    prog_idle_s: Dict[str, float] = dataclasses.field(default_factory=dict)


class Busy:
    """One device's merged busy intervals, with prefix sums: the busy
    time inside any interval in O(log n), exact in integer nanoseconds."""

    def __init__(self, merged: Sequence[trace.Interval]):
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]
        self.cum = [0]
        for a, b in merged:
            self.cum.append(self.cum[-1] + b - a)

    def before(self, t: int) -> int:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0
        return self.cum[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]

    def overlap(self, a: int, b: int) -> int:
        return self.before(b) - self.before(a) if b > a else 0


def _over_gaps(gaps: Sequence[trace.Interval], spans: Sequence[tuple]):
    """For each gap, in order, the indices (ascending) of the spans that
    start before its end and end after its start. Gaps are sorted and
    disjoint, so a span that ends before one gap's start is done."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    k, active = 0, []
    for a, b in gaps:
        while k < len(order) and spans[order[k]][1] < b:
            active.append(order[k])
            k += 1
        active = [i for i in active if spans[i][2] > a]
        yield sorted(active)


def _benchmark_label(a: int, b: int, named: Sequence[Span],
                     hits: List[int]) -> str:
    """The name `trace.reduce_events` gives the gap [a, b): the benchmark
    span name covering most of it, first seen on a tie, else `outside
    spans`."""
    cover: Dict[str, int] = {}
    for i in hits:
        n, x, y = named[i]
        ov = max(0, min(b, y) - max(a, x))
        if ov:
            cover[n] = cover.get(n, 0) + ov
    covered = sum(y - x for x, y in trace.merge(
        (max(a, named[i][1]), min(b, named[i][2])) for i in hits))
    cover["outside spans"] = (b - a) - covered
    return max(cover, key=cover.get)


def _held(a: int, b: int, prog: Sequence[ProgramSpan],
          hits: List[int]) -> Dict[str, int]:
    """How much of [a, b) each program span name holds when each instant
    goes to the innermost span over it (the latest to start, the shortest
    on a tie)."""
    cuts = sorted({a, b} | {t for i in hits for t in prog[i][1:3]
                            if a < t < b})
    held: Dict[str, int] = {}
    for x, y in zip(cuts, cuts[1:]):
        over = [prog[i] for i in hits if prog[i][1] <= x and prog[i][2] >= y]
        if over:
            n = max(over, key=lambda s: (s[1], s[1] - s[2]))[0]
            held[n] = held.get(n, 0) + (y - x)
    return held


def reduce_events(device_ops: Dict[str, List[Span]], spans: List[Span],
                  program_spans: Sequence[ProgramSpan] = (), top: int = 10
                  ) -> Reduced:
    """`device_ops`: plane -> [(op name, start ns, end ns)]; `spans`: the
    benchmark's [(name, start ns, end ns)], the window among them;
    `program_spans`: [(name, start ns, end ns, stats)], on the same
    clock."""
    wins = [(a, b) for n, a, b in spans if n == trace.WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {trace.WINDOW!r} span, found "
                         f"{len(wins)}")
    w0, w1 = wins[0]
    named = [(n, a, b) for n, a, b in spans if n != trace.WINDOW]
    last = max((min(b, w1) for ops in device_ops.values()
                for _, a, b in ops if b > w0 and a < w1), default=None)
    cut = 0
    if last is not None and any(a >= last for _, a, _ in named):
        cut, w1 = w1 - last, last
        named = [(n, a, b) for n, a, b in named if b <= last]
    busy, per_op, busy_by_dev = [], {}, {}
    for plane, ops in device_ops.items():
        clipped = [(max(a, w0), min(b, w1), name) for name, a, b in ops
                   if b > w0 and a < w1]
        if not clipped:
            continue
        for a, b, name in clipped:
            label = trace.op_label(name)
            per_op[label] = per_op.get(label, 0) + (b - a)
        m = trace.merge((a, b) for a, b, _ in clipped)
        busy_by_dev[plane] = (m, Busy(m))
        busy.append(sum(b - a for a, b in m))
    if not busy_by_dev:
        raise ValueError("no device operation ran inside the window")
    n_dev = len(busy_by_dev)
    span_busy, span_count, span_host = {}, {}, {}
    for n, a, b in named:
        span_count[n] = span_count.get(n, 0) + 1
        span_busy[n] = span_busy.get(n, 0.0) + sum(
            bz.overlap(a, b) for _, bz in busy_by_dev.values()
        ) / n_dev / 1e9
        span_host[n] = span_host.get(n, 0.0) + (b - a) / 1e9
    # each device's idle gaps, listed together
    prog = list(program_spans)
    gaps, idle_held = [], {}
    for m, _ in busy_by_dev.values():
        edges = [w0] + [x for iv in m for x in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for (a, b), hits, phits in zip(idle, _over_gaps(idle, named),
                                       _over_gaps(idle, prog)):
            held = _held(a, b, prog, phits)
            for n, t in held.items():
                idle_held[n] = idle_held.get(n, 0) + t
            label = (max(held, key=held.get) if held
                     else _benchmark_label(a, b, named, hits))
            gaps.append((label, (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    host, count, stats = _program_totals(prog, named, w0, w1)
    return Reduced(window_s=(w1 - w0) / 1e9,
                   busy_s=sum(busy) / n_dev / 1e9,
                   top_ops=[(n, t / 1e9) for n, t in ops],
                   idle_gaps=gaps[:top], span_busy_s=span_busy,
                   span_count=span_count, cut_s=cut / 1e9,
                   span_host_s=span_host, prog_host_s=host,
                   prog_count=count, prog_stats=stats,
                   prog_idle_s={n: t / n_dev / 1e9
                                for n, t in idle_held.items()})


def _program_totals(prog: Sequence[ProgramSpan], named: Sequence[Span],
                    w0: int, w1: int):
    """Host seconds, count and stat sums per program span name, of the
    spans that end inside [w0, w1] and lie inside a kept benchmark span."""
    kept = sorted((a, b) for _, a, b in named)
    starts = [a for a, _ in kept]
    reach, r = [], None           # the latest end among spans so far
    for _, b in kept:
        r = b if r is None else max(r, b)
        reach.append(r)
    host: Dict[str, float] = {}
    count: Dict[str, int] = {}
    stats: Dict[str, Dict[str, float]] = {}
    for n, a, b, st in prog:
        if not w0 <= b <= w1:
            continue
        i = bisect.bisect_right(starts, a)
        if i == 0 or reach[i - 1] < b:
            continue
        host[n] = host.get(n, 0.0) + (b - a) / 1e9
        count[n] = count.get(n, 0) + 1
        sums = stats.setdefault(n, {})
        for k, v in st.items():
            if isinstance(v, (int, float)):
                sums[k] = sums.get(k, 0) + v
    return host, count, stats


def read_xplane(path: str, span_names: Iterable[str],
                program_names: Iterable[str] = ()):
    """The device operations, the benchmark's spans and the program's
    spans (with their stats) of one trace."""
    from jax.profiler import ProfileData

    want = set(span_names) | {trace.WINDOW}
    prog_want = set(program_names)
    pd = ProfileData.from_file(path)
    device_ops, spans, prog = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        (e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name in want:
                        spans.append((name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)))
                    elif name in prog_want:
                        prog.append((name, int(e.start_ns),
                                     int(e.start_ns + e.duration_ns),
                                     dict(e.stats)))
    return device_ops, spans, prog


def reduce_file(path: str, span_names: Iterable[str],
                program_names: Iterable[str] = ()) -> Reduced:
    return reduce_events(*read_xplane(path, span_names, program_names))
