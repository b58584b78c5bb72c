"""Spans and counters recorded inside the program (DESIGN.md §18).

One recorder for every host timer of the data plane. A span is
``(name, t0, t1, thread, id, parent, n)``: its start and end on
``time.perf_counter_ns()``, the thread that closed it, its own id, the id
of the span that caused it, and a count measured at the boundary (bytes,
rows). ``parent`` comes from a per-thread stack of open spans; work handed
to another thread names its parent explicitly (:func:`under`,
:func:`record`).

The clock is the one the benchmark harness reads for its own spans and
for the start of the device trace, so a span here lines up with the
device's operations to about a millisecond. Recording is always on: a
span costs two clock reads and one append to a process-wide ring of the
last :data:`CAPACITY` spans, with no lock (``deque.append`` and
``next()`` on an ``itertools.count`` are atomic under the GIL).
:func:`recorded` counts every span ever appended, so a reader can tell
when the ring dropped spans it wanted.

Counters (:func:`counter`) are process-wide totals, such as the codec's
chunk reads; each takes its own lock to add.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

CAPACITY = 1 << 16

now = time.perf_counter_ns

_ring: collections.deque = collections.deque(maxlen=CAPACITY)
_appends = itertools.count()  # one next() per append, taken before it,
# and one per recorded() call, which reading it costs
_reads_lock = threading.Lock()
_reads = 0  # guarded-by: _reads_lock
_ids = itertools.count(1)
_local = threading.local()


class Span(NamedTuple):
    name: str
    t0: int          # ns, time.perf_counter_ns()
    t1: int
    thread: int      # threading.get_ident() of the thread that closed it
    id: int
    parent: Optional[int]
    n: int           # count at the boundary: bytes, rows

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9


def _stack() -> List[int]:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


def current() -> Optional[int]:
    """The id of this thread's innermost open span, or None."""
    s = _stack()
    return s[-1] if s else None


def new_id() -> int:
    """A span id taken ahead of :func:`record`, for a span whose children
    start before it ends."""
    return next(_ids)


def _append(span: Span) -> Span:
    next(_appends)
    _ring.append(span)
    return span


def record(name: str, t0: int, t1: int, *, parent: Optional[int] = None,
           n: int = 0, id: Optional[int] = None) -> Span:
    """Record a span whose start and end were read elsewhere, such as one
    that starts on one thread and ends on another; ``parent`` defaults to
    this thread's innermost open span."""
    return _append(Span(name, t0, t1, threading.get_ident(), next(_ids) if id is None else id,
                        current() if parent is None else parent, n))


class _Open:
    """A span being timed; ``seconds`` and ``n`` may be read and set by the
    caller (``n`` before the span closes)."""

    __slots__ = ("name", "n", "id", "parent", "t0", "t1")

    def __init__(self, name: str, n: int):
        self.name, self.n = name, n
        self.id = next(_ids)
        self.t0 = self.t1 = 0

    def __enter__(self) -> "_Open":
        s = _stack()
        self.parent = s[-1] if s else None
        s.append(self.id)
        self.t0 = now()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = now()
        _stack().pop()
        _append(Span(self.name, self.t0, self.t1, threading.get_ident(), self.id,
                     self.parent, self.n))

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9


def span(name: str, n: int = 0) -> _Open:
    """Time the ``with`` block as one span, the child of this thread's
    innermost open span."""
    return _Open(name, n)


class under:
    """Run the ``with`` block as if span ``parent`` were open on this
    thread: spans opened inside name it as their parent. For pool tasks
    working on behalf of a span opened elsewhere."""

    __slots__ = ("parent",)

    def __init__(self, parent: int):
        self.parent = parent

    def __enter__(self) -> None:
        _stack().append(self.parent)

    def __exit__(self, *exc) -> None:
        _stack().pop()


def recorded() -> int:
    """Spans recorded since the process started; more than the ring holds
    means the oldest were dropped."""
    global _reads
    with _reads_lock:  # reads in turn, so each subtracts the ones before it
        n = next(_appends) - _reads
        _reads += 1
    return n


def spans(t0: Optional[int] = None, t1: Optional[int] = None) -> List[Span]:
    """A snapshot of the ring, oldest first. With a window ``[t0, t1)``,
    only the spans that overlap it, each clipped to it."""
    snap = list(_ring)
    if t0 is None and t1 is None:
        return snap
    lo = t0 if t0 is not None else -1 << 63
    hi = t1 if t1 is not None else 1 << 63
    return [s._replace(t0=max(s.t0, lo), t1=min(s.t1, hi))
            for s in snap if s.t1 > lo and s.t0 < hi]


# ------------------------------------------------------------------ counters
class Counter:
    """A process-wide total."""

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self.value = 0  # guarded-by: _lock

    def add(self, k: int = 1) -> None:
        with self._lock:
            self.value += k

    def reset(self) -> None:
        with self._lock:
            self.value = 0


_counters_lock = threading.Lock()
_counters: Dict[str, Counter] = {}  # guarded-by: _counters_lock


def counter(name: str) -> Counter:
    """The counter named ``name``, made on first use; hold on to it rather
    than look it up on a hot path."""
    with _counters_lock:
        c = _counters.get(name)
        if c is None:
            c = _counters[name] = Counter(name)
        return c
