"""Reduce a JAX profiler trace to device busy time, kernel time and gaps.

    python bench/trace.py <file.xplane.pb>    # print what the trace holds

The profiler writes one ``.xplane.pb``. Each TPU is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation
that ran on that chip, named by the op's HLO text, with a start and a
duration in nanoseconds. A Mosaic kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"``; the kernels carry no name of
their own there, so a reader tells them apart by their output. The
host is the plane ``/host:CPU``; the harness marks its own phases there
with ``jax.profiler.TraceAnnotation`` (names starting ``bench.``), and
``bench.traced_window`` spans the traced cycles. The two planes' clocks
agree to about a millisecond.

From that, on each chip: busy time is the union of the op intervals
inside the window, a kernel's time is the sum of its events' durations,
and an idle gap is a stretch of the window with no op running, named
after the host phase that covered most of it.
"""

from __future__ import annotations

import dataclasses
import re
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float, str]     # start_ns, end_ns, name

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_PREFIX = "bench."
WINDOW = "bench.traced_window"


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Interval]]   # plane name -> op events
    host: List[Interval]                 # the harness's annotations
    window: Tuple[float, float]          # start_ns, end_ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs: List[Interval] = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for e in line.events:
                    evs.append((float(e.start_ns),
                                float(e.start_ns) + float(e.duration_ns),
                                e.name))
            devices[plane.name] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((float(e.start_ns),
                                     float(e.start_ns) + float(e.duration_ns),
                                     e.name))
    wins = [h for h in host if h[2] == WINDOW]
    if not wins:
        raise ValueError(f"{path}: no {WINDOW!r} annotation on {HOST_PLANE}")
    # the chips' clock runs about a millisecond off the host's in these
    # traces, so the window also spans every op the profile caught
    lo, hi = wins[0][0], wins[0][1]
    for evs in devices.values():
        if evs:
            lo = min(lo, min(s for s, _, _ in evs))
            hi = max(hi, max(e for _, e, _ in evs))
    return Trace(devices, host, (lo, hi))


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The union of intervals as sorted, disjoint (start, end) pairs."""
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_s(trace: Trace) -> Dict[str, float]:
    """Seconds in the window in which some op ran, per chip."""
    lo, hi = trace.window
    return {d: sum(e - s for s, e in clip(merge(evs), lo, hi)) * 1e-9
            for d, evs in trace.devices.items()}


def mean_busy_s(trace: Trace) -> float:
    per = busy_s(trace)
    return sum(per.values()) / len(per) if per else 0.0


def kernel_time(trace: Trace, pattern: str) -> Tuple[float, int]:
    """Summed device seconds and count of the op events in the window
    whose name matches ``pattern``, over every chip."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    total, count = 0.0, 0
    for evs in trace.devices.values():
        for s, e, name in evs:
            if s >= lo and e <= hi and rx.search(name):
                total += e - s
                count += 1
    return total * 1e-9, count


_OP = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?(?: =|$)")


def op_kind(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``: an event's
    name is the op's HLO text; its kind is the instruction's name
    without the numeric suffix."""
    m = _OP.match(name)
    return m.group(1) if m else name


# ops whose event spans the ops of their body
CONTAINERS = ("while", "conditional", "call")


def op_totals(trace: Trace, top: int = 10) -> List[List]:
    """The kinds of op that took most device time in the window,
    averaged over chips (see ``op_kind``); loops and calls, whose events
    span their bodies' ops, are left out."""
    lo, hi = trace.window
    tot: Dict[str, float] = defaultdict(float)
    for evs in trace.devices.values():
        for s, e, name in evs:
            kind = op_kind(name)
            if s >= lo and e <= hi and kind not in CONTAINERS:
                tot[kind] += (e - s) * 1e-9
    n = max(len(trace.devices), 1)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / n] for k, v in ranked]


def idle_gaps(trace: Trace, top: int = 10) -> List[List]:
    """The longest stretches of the window with no op on a chip, each
    named after the host phase that overlaps it most (``host.none``
    where none does), as [name, seconds], longest first."""
    lo, hi = trace.window
    phases = [h for h in trace.host if h[2] != WINDOW]
    gaps: List[Tuple[float, str]] = []
    for evs in trace.devices.values():
        busy = clip(merge(evs), lo, hi)
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            best, label = 0.0, "host.none"
            for hs, he, name in phases:
                ov = min(ge, he) - max(gs, hs)
                if ov > best:
                    best, label = ov, name
            gaps.append(((ge - gs) * 1e-9, label))
    gaps.sort(key=lambda g: -g[0])
    return [[label, secs] for secs, label in gaps[:top]]


def describe(path: str) -> None:
    """Print the planes, lines and a few events of a trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for e in evs[:3]:
                print(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns}"
                      f" stats={list(e.stats)[:6]}")


if __name__ == "__main__":
    describe(sys.argv[1])
