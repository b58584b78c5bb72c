"""What the readers of the program's own spans share.

The program records spans in ``repro.core.obs`` on ``time.perf_counter_ns()``,
the clock the harness reads for its own spans and for ``run._trace_t0``, so a
program span sits on the device trace's clock at ``t - run._trace_t0``. A
reader takes the program's spans inside the ``bench.window`` span, clipped to
it, and returns nothing where there are none to read: a program without
``repro.core.obs``, an untraced run, or a ring that dropped spans inside the
window.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench import trace

# the feeder thread's states, for attributing the device's idle gaps
FEEDER = ("device_loader.h2d", "device_loader.dequant", "device_loader.put_wait", "loader.wait")


def window(run) -> Optional[Tuple[int, int]]:
    """The window on the program's clock, from the harness's span."""
    for name, start, end in run.spans:
        if name == trace.WINDOW:
            return run._trace_t0 + start, run._trace_t0 + end
    return None


def program_spans(run) -> Optional[list]:
    """The program's spans inside the window, clipped to it."""
    try:
        from repro.core import obs
    except ImportError:
        return None
    win = window(run)
    if win is None:
        return None
    lo, hi = win
    kept = obs.spans()
    if obs.recorded() > len(kept) and min((s.t1 for s in kept), default=lo) >= lo:
        # the ring dropped its oldest spans and holds none that ended
        # before the window: some of the window's may be among them
        return None
    return obs.spans(lo, hi)


def named(spans, name: str) -> list:
    return [s for s in spans if s.name == name]


def _overlap(spans, lo: int, hi: int) -> float:
    return sum(max(0, min(s.t1, hi) - max(s.t0, lo)) for s in spans) / 1e9


def restores(run) -> Optional[List[Dict[str, float]]]:
    """For each ``restore`` span in the window that streamed chunks, its
    parts in seconds: ``restore_s``; ``stream_s``, from its first
    ``codec.fetch`` start to its last ``codec.inflate`` end; the summed
    durations of its ``codec.fetch`` and ``codec.inflate`` spans
    (thread-seconds); and the summed durations of its ``restore.pin``,
    ``restore.admit_wait``, ``restore.h2d``, ``restore.drain`` and
    ``restore.warmup`` spans, with the part of the warm-up inside the stream.
    A span belongs to the restore among its ancestors."""
    spans = program_spans(run)
    if spans is None:
        return None
    by_id = {s.id: s for s in spans}
    mine: Dict[int, list] = {s.id: [] for s in spans if s.name == "restore"}
    for s in spans:
        p = by_id.get(s.parent)
        while p is not None and p.name != "restore":
            p = by_id.get(p.parent)
        if p is not None:
            mine[p.id].append(s)
    out = []
    for rid, parts in mine.items():
        fetch = named(parts, "codec.fetch")
        inflate = named(parts, "codec.inflate")
        if not fetch or not inflate:
            continue
        lo, hi = min(s.t0 for s in fetch), max(s.t1 for s in inflate)
        row = {"restore_s": by_id[rid].seconds, "stream_s": (hi - lo) / 1e9,
               "fetch_thread_s": sum(s.seconds for s in fetch),
               "inflate_thread_s": sum(s.seconds for s in inflate),
               "warmup_in_stream_s": _overlap(named(parts, "restore.warmup"), lo, hi)}
        for name in ("pin", "admit_wait", "h2d", "drain", "warmup"):
            row[name + "_s"] = sum(s.seconds for s in named(parts, "restore." + name))
        out.append(row)
    return out


def mean(rows: Optional[List[Dict[str, float]]], part: str) -> Optional[float]:
    """The mean of one part of :func:`restores` over the window's cold
    starts."""
    if not rows:
        return None
    return sum(r[part] for r in rows) / len(rows)


def per_batch_ms(run, *names: str) -> Optional[float]:
    """Milliseconds of the spans named ``names`` in the window per batch the
    feeder took from the host loader (its ``loader.wait`` spans)."""
    spans = program_spans(run)
    if spans is None:
        return None
    batches = len(named(spans, "loader.wait"))
    if not batches:
        return None
    return sum(s.seconds for s in spans if s.name in names) / batches * 1e3


def idle_by_state(devices: Dict[str, List[trace.Event]], window_event: trace.Event,
                  host: List[trace.Event]) -> Dict[str, float]:
    """Seconds of device 0's idle gaps in the window by the host span that
    holds each gap's midpoint (``outside spans`` where none does), through
    the harness's own gap reduction."""
    s = trace.summarize(trace.Trace(devices, [window_event] + host))
    out: Dict[str, float] = {}
    for name, sec in s.gaps:
        out[name] = out.get(name, 0.0) + sec
    return out


def feeder_idle(run) -> Optional[Dict[str, float]]:
    """The device's idle seconds in the window by the feeder's state."""
    spans = program_spans(run)
    if spans is None or run.trace_dir is None:
        return None
    t = run._trace_t0
    lo, hi = window(run)
    host = [trace.Event(s.name, s.t0 - t, s.t1 - t) for s in spans if s.name in FEEDER]
    devices = trace.read(trace.find(run.trace_dir))
    return idle_by_state(devices, trace.Event(trace.WINDOW, lo - t, hi - t), host)
