"""Reduce a profiler trace (`.xplane.pb`) to what the benchmark reports.

Device planes are those named `/device:...`; an operation is an event on a
plane's `XLA Ops` line. Host spans are the benchmark's own
`jax.profiler.TraceAnnotation`s, found by name on the host planes; the
span named `window` bounds the measured window. From these:

* busy time: the union of operation intervals inside the window, per
  device, averaged over the devices that ran any operation;
* top device operations by total time inside the window;
* idle gaps inside the window, each labelled with the benchmark span that
  covers most of it (what the host was doing), longest first;
* busy time and count per span name, for per-layer readers.

The profiler keeps a bounded number of device events: on a v5e a 45 s
fleet window of some 500 short calls lost its device events after about
15 s, while the host went on calling (three traced runs, the same busy
time to four digits, one idle gap of 28-30 s). Where a benchmark span
starts after the last device operation, the device trace is taken to
end there: the readings cover the window up to the last device operation,
and the spans that end inside it.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
WINDOW = "window"

Interval = Tuple[int, int]
_STATE_ARG = re.compile(r"%(state_[A-Za-z0-9_]+?)(?:\.\d+)?[ ,)]")


def op_label(name: str) -> str:
    """An XLA op's event name is its whole HLO text: keep the op's name,
    and the first piece of train state it reads, where it reads one."""
    short = name.split(" = ", 1)[0]
    m = _STATE_ARG.search(name)
    return f"{short} {m.group(1)}" if m else short


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(merged: Sequence[Interval], a: int, b: int) -> int:
    return sum(max(0, min(b, y) - max(a, x)) for x, y in merged
               if y > a and x < b)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                     # mean over devices that ran ops
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    span_busy_s: Dict[str, float]     # device busy inside spans, per name
    span_count: Dict[str, int]
    cut_s: float = 0.0                # window left out: no device events

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def reduce_events(device_ops: Dict[str, List[Tuple[str, int, int]]],
                  spans: List[Tuple[str, int, int]], top: int = 10
                  ) -> Reduced:
    """`device_ops`: plane -> [(op name, start ns, end ns)];
    `spans`: [(span name, start ns, end ns)] on the same clock."""
    wins = [(a, b) for n, a, b in spans if n == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(wins)}")
    w0, w1 = wins[0]
    named = [(n, a, b) for n, a, b in spans if n != WINDOW]
    last = max((min(b, w1) for ops in device_ops.values()
                for _, a, b in ops if b > w0 and a < w1), default=None)
    cut = 0
    if last is not None and any(a >= last for _, a, _ in named):
        cut, w1 = w1 - last, last
        named = [(n, a, b) for n, a, b in named if b <= last]
    busy, per_op = [], {}
    merged_by_dev = {}
    for plane, ops in device_ops.items():
        clipped = [(max(a, w0), min(b, w1), name) for name, a, b in ops
                   if b > w0 and a < w1]
        if not clipped:
            continue
        for a, b, name in clipped:
            label = op_label(name)
            per_op[label] = per_op.get(label, 0) + (b - a)
        m = merge((a, b) for a, b, _ in clipped)
        merged_by_dev[plane] = m
        busy.append(sum(b - a for a, b in m))
    if not merged_by_dev:
        raise ValueError("no device operation ran inside the window")
    n_dev = len(merged_by_dev)
    span_busy, span_count = {}, {}
    for n, a, b in named:
        span_count[n] = span_count.get(n, 0) + 1
        span_busy[n] = span_busy.get(n, 0.0) + sum(
            overlap(m, a, b) for m in merged_by_dev.values()) / n_dev / 1e9
    # each device's idle gaps, listed together
    gaps = []
    for m in merged_by_dev.values():
        edges = [w0] + [x for iv in m for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                cover = {}
                for n, x, y in named:
                    ov = max(0, min(b, y) - max(a, x))
                    if ov:
                        cover[n] = cover.get(n, 0) + ov
                covered = sum(y - x for x, y in merge(
                    (max(a, x), min(b, y)) for _, x, y in named
                    if y > a and x < b))
                cover["outside spans"] = (b - a) - covered
                gaps.append((max(cover, key=cover.get), (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(window_s=(w1 - w0) / 1e9,
                   busy_s=sum(busy) / n_dev / 1e9,
                   top_ops=[(n, t / 1e9) for n, t in ops],
                   idle_gaps=gaps[:top], span_busy_s=span_busy,
                   span_count=span_count, cut_s=cut / 1e9)


def read_xplane(path: str, span_names: Iterable[str]):
    """The device operations and the named host spans of one trace."""
    from jax.profiler import ProfileData

    want = set(span_names) | {WINDOW}
    pd = ProfileData.from_file(path)
    device_ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        (e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                             for e in line.events if e.name in want)
    return device_ops, spans


def reduce_file(path: str, span_names: Iterable[str]) -> Reduced:
    device_ops, spans = read_xplane(path, span_names)
    return reduce_events(device_ops, spans)
