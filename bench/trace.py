"""Reduce a profiler trace of the window to device busy time, idle gaps,
per-operation and per-kernel device time.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes.
Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, on the trace's clock (nanoseconds since the
trace started). Host spans are the benchmark's own, kept by the harness on
the same clock: ``bench.window`` around the window, and the spans of the
generators' calls into each layer.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"

Interval = Tuple[int, int]  # [start_ns, end_ns)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over devices of the union of ops
    op_s: Dict[str, float]             # summed device time by op name
    kernel_calls: Dict[str, List[Tuple[str, float]]]  # by kernel: (op name, seconds) per call
    gaps: List[Tuple[str, float]]      # idle gaps of device 0, named by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        by_span: Dict[str, float] = {}
        for name, s in self.gaps:
            by_span[name] = by_span.get(name, 0.0) + s
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": [[n, s] for n, s in gaps]}


@dataclass
class Event:
    name: str
    start: int
    end: int


@dataclass
class Trace:
    devices: Dict[str, List[Event]]      # plane name -> its op events
    host: List[Event]                    # the benchmark's host spans


def read(path: str) -> Dict[str, List[Event]]:
    """The device operations of one ``.xplane.pb``, by plane."""
    from jax.profiler import ProfileData

    devices: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            evs = [Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE for e in line.events]
            if evs:
                devices[plane.name] = evs
    return devices


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def summarize(trace: Trace, kernels: Optional[Dict[str, str]] = None) -> Summary:
    """``kernels`` maps a kernel's name in the metrics to a regular
    expression that its device operations' names match (an op's name is its
    HLO instruction); each match inside the window is one call."""
    wins = [e for e in trace.host if e.name == WINDOW]
    if not wins or not trace.devices:
        raise ValueError("trace holds no window span or no device operations")
    lo, hi = wins[0].start, wins[0].end
    window_s = (hi - lo) / 1e9
    busy, op_s = [], {}
    kernel_calls: Dict[str, List[Tuple[str, float]]] = {k: [] for k in (kernels or {})}
    first = sorted(trace.devices)[0]
    first_busy: List[Interval] = []
    for plane, evs in trace.devices.items():
        inside = [e for e in evs if e.end > lo and e.start < hi]
        u = union(clip([(e.start, e.end) for e in inside], lo, hi))
        busy.append(sum(e - s for s, e in u) / 1e9)
        if plane == first:
            first_busy = u
        for e in inside:
            dur = (min(e.end, hi) - max(e.start, lo)) / 1e9
            op_s[e.name] = op_s.get(e.name, 0.0) + dur
            for k, pattern in (kernels or {}).items():
                if re.search(pattern, e.name):
                    kernel_calls[k].append((e.name, (e.end - e.start) / 1e9))
    # idle gaps of the first device, each named by the innermost benchmark
    # span that holds its midpoint
    spans = sorted((e for e in trace.host if e.name != WINDOW), key=lambda h: h.start)
    gaps: List[Tuple[str, float]] = []
    active: List[Event] = []
    nxt = 0
    prev = lo
    for s, e in first_busy + [(hi, hi)]:
        if s > prev:
            mid = (prev + s) // 2
            while nxt < len(spans) and spans[nxt].start <= mid:
                active.append(spans[nxt])
                nxt += 1
            active = [h for h in active if h.end > mid]
            name = min(active, key=lambda h: h.end - h.start).name if active else "outside spans"
            gaps.append((name, (s - prev) / 1e9))
        prev = max(prev, e)
    return Summary(window_s=window_s, busy_s=sum(busy) / len(busy), op_s=op_s,
                   kernel_calls=kernel_calls, gaps=gaps)


def reduce(trace_dir: str, spans: List[tuple], kernels: Optional[Dict[str, str]] = None) -> Summary:
    """Summarize the trace under ``trace_dir`` with the harness's host
    spans, ``(name, start_ns, end_ns)``."""
    host = [Event(n, s, e) for n, s, e in spans]
    return summarize(Trace(read(find(trace_dir)), host), kernels)
