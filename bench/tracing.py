"""Profiler capture and the reduction from a trace to numbers.

A traced run wraps a few seconds of the window in ``jax.profiler``.  The
reduction reads the ``.xplane.pb`` it writes with ``ProfileData`` alone:

* device busy time is the union of the intervals of the device's op
  events, clipped to the traced window (the span from the first to the
  last host annotation of the loop);
* every idle gap of the device is put down to the loop annotation
  (``bench.step``, ``bench.submit``, ``bench.collect``) that covers most of
  it on the host, or to ``host`` where none does;
* a kernel's time is the sum of the durations of its events, found by a
  substring of the name the trace shows;
* the device ops that took most time, summed by name, go to ``breakdown``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

#: preferred line of a device plane: one event per executed HLO op
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    idle_by_host: Dict[str, float]  # idle seconds by what the host did
    idle_gaps: List[Tuple[str, float]]  # longest gaps, (host, seconds)
    device_ops: List[Tuple[str, float]]  # (op name, seconds), most first
    op_seconds: Dict[str, float]  # every op name -> seconds
    n_devices: int

    def kernel_seconds(self, pattern: str) -> float:
        return sum(s for n, s in self.op_seconds.items() if pattern in n)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def op_name(text: str) -> str:
    """``%name.3 = f32[...] op(...)`` (an HLO line, as TPU traces name
    their ops) -> ``name.3``; a plain name is kept."""
    return text.split(" = ", 1)[0].lstrip("%")


def device_events(planes) -> List[List[Event]]:
    """Op events of each device plane (TPU or GPU, not the host)."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        lines = list(plane.lines)
        ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
        evs = [Event(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
               for ln in ops for e in ln.events if e.duration_ns > 0]
        if evs:
            out.append(evs)
    return out


def host_events(planes, names: Sequence[str]) -> List[Event]:
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name in names:
                    out.append(Event(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return out


def reduce(planes, annotations: Sequence[str], top: int = 10) -> Optional[Summary]:
    """Summary of a trace, or None when it holds no device op or no
    loop annotation."""
    devs = device_events(planes)
    host = host_events(planes, annotations)
    if not devs or not host:
        return None
    host.sort(key=lambda e: e.start_ns)
    starts = [e.start_ns for e in host]
    lo = starts[0]
    hi = max(e.end_ns for e in host)
    busy_total = 0.0
    idle: Dict[str, float] = {}
    all_gaps: List[Tuple[str, float]] = []
    ops: Dict[str, float] = {}
    for evs in devs:
        busy = clip(union((e.start_ns, e.end_ns) for e in evs), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for g in gaps(busy, lo, hi):
            best, name = 0.0, "host"
            # loop annotations are short and barely nested: the few that
            # start just before the gap's end are the ones that cover it
            j = bisect.bisect_left(starts, g[1])
            for h in host[max(0, j - 16):j]:
                ov = overlap(g, (h.start_ns, h.end_ns))
                if ov > best:
                    best, name = ov, h.name
            sec = (g[1] - g[0]) / 1e9
            idle[name] = idle.get(name, 0.0) + sec
            all_gaps.append((name, sec))
        for e in evs:
            d = overlap((e.start_ns, e.end_ns), (lo, hi))
            if d > 0:
                ops[e.name] = ops.get(e.name, 0.0) + d / 1e9
    n = len(devs)
    window_s = (hi - lo) / 1e9
    all_gaps.sort(key=lambda g: -g[1])
    return Summary(
        window_s=window_s,
        busy_s=busy_total / n / 1e9,
        idle_by_host={k: v / n for k, v in idle.items()},
        idle_gaps=all_gaps[:top],
        device_ops=sorted(((k, v / n) for k, v in ops.items()),
                          key=lambda kv: -kv[1])[:top],
        op_seconds={k: v / n for k, v in ops.items()},
        n_devices=n,
    )


def find_xplane(logdir: str) -> Optional[str]:
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load_planes(path: str):
    from jax.profiler import ProfileData

    return list(ProfileData.from_file(path).planes)
