"""Tests of the readers of the program's own spans (``bench/program_spans.py``
and the metrics that use it), on the CPU: clipping to the window, nothing
read where the ring dropped spans inside it, the idle attribution on a
hand-made gap pattern, and, at tiny size through the harness's generators,
the program's spans inside the benchmark's own spans on one clock.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

import jax  # noqa: E402

from bench import harness, program_spans, trace  # noqa: E402
from bench.tests import tiny  # noqa: E402
from repro.core import obs  # noqa: E402

# CPU programs stay out of the checkout's compile cache
jax.config.update("jax_enable_compilation_cache", False)

FEED, COLD = "feed.imagenet.u8", "coldstart.olmo1b.url_q8"


def _reader(cell, name):
    return harness.load_module(harness.resolve(cell, REPO).metric_file(name)).read


def _run(cell, lo, hi, t0=None, trace_dir=None):
    """A traced run whose window is [lo, hi) on the program's clock."""
    t0 = lo - 1000 if t0 is None else t0
    return harness.Run(cell=harness.resolve(cell, REPO), seed=1, seconds=1.0, trace=True,
                       t_process=0.0, workdir="", spans=[(trace.WINDOW, lo - t0, hi - t0)],
                       trace_dir=trace_dir, _trace_t0=t0)


@pytest.fixture()
def later():
    """A window of the program's clock that no span has reached yet."""
    lo = obs.now() + 10**15
    return lo, lo + 10_000


# ------------------------------------------------------------------ clipping
@pytest.mark.parametrize("read", ["dataset.gather", "dataset.rows"])
def test_feed_spans_are_clipped_to_the_window(later, read):
    lo, hi = later
    for name, a, b in [("loader.wait", lo - 50, lo + 50), ("loader.wait", lo + 100, lo + 300),
                       ("loader.wait", hi + 10, hi + 20),
                       (read, lo - 1000, lo + 1000),
                       (read, hi - 500, hi + 500),
                       (read, lo - 30, lo - 20)]:
        obs.record(name, a, b)
    run = _run(FEED, lo, hi)
    # two waits inside (50 ns clipped and 200 ns); gathers 1000 + 500 ns clipped
    assert _reader(FEED, "loader_wait_ms.feed")(run, None, {}) == pytest.approx(250e-9 / 2 * 1e3)
    assert _reader(FEED, "gather_ms.feed")(run, None, {}) == pytest.approx(1500e-9 / 2 * 1e3)


def test_restore_parts_follow_the_span_tree_and_the_window(later):
    lo, hi = later
    rid, leaf = obs.new_id(), obs.new_id()
    obs.record("restore", lo + 100, lo + 9000, id=rid)
    obs.record("restore.leaf", lo + 200, lo + 8000, parent=rid, id=leaf)
    for name, a, b in [("codec.fetch", lo + 300, lo + 1300), ("codec.fetch", lo + 400, lo + 900),
                       ("codec.inflate", lo + 1300, lo + 2300),
                       ("codec.inflate", lo + 900, lo + 2900)]:
        obs.record(name, a, b, parent=leaf)
    obs.record("restore.warmup", lo + 150, lo + 600, parent=rid)
    # a chunk of no restore, and one of a restore that ends after the window
    obs.record("codec.fetch", lo + 10, lo + 5000)
    rid2, leaf2 = obs.new_id(), obs.new_id()
    obs.record("restore", hi - 1000, hi + 5000, id=rid2)
    obs.record("restore.leaf", hi - 900, hi + 4000, parent=rid2, id=leaf2)
    obs.record("codec.fetch", hi - 800, hi + 100, parent=leaf2)
    obs.record("codec.inflate", hi + 100, hi + 200, parent=leaf2)
    run = _run(COLD, lo, hi)
    rows = program_spans.restores(run)
    assert [r["stream_s"] for r in rows] == [pytest.approx(2600e-9)]
    assert rows[0]["fetch_thread_s"] == pytest.approx(1500e-9)
    assert rows[0]["inflate_thread_s"] == pytest.approx(3000e-9)
    assert rows[0]["warmup_in_stream_s"] == pytest.approx(300e-9)
    assert _reader(COLD, "stream_s.coldstart")(run, None, {}) == pytest.approx(2600e-9)
    assert run.counters["restore.restore_s"] == pytest.approx(8900e-9)
    assert _reader(COLD, "fetch_thread_s.coldstart")(run, None, {}) == pytest.approx(1500e-9)
    assert _reader(COLD, "inflate_thread_s.coldstart")(run, None, {}) == pytest.approx(3000e-9)


@pytest.mark.parametrize("cell,metric", [(FEED, "gather_ms.feed"), (FEED, "loader_wait_ms.feed"),
                                         (FEED, "idle_in_h2d_share.feed"),
                                         (COLD, "stream_s.coldstart"),
                                         (COLD, "fetch_thread_s.coldstart"),
                                         (COLD, "inflate_thread_s.coldstart")])
def test_nothing_is_read_without_a_window(cell, metric):
    run = harness.Run(cell=harness.resolve(cell, REPO), seed=1, seconds=1.0, trace=True,
                      t_process=0.0, workdir="")
    assert _reader(cell, metric)(run, None, {}) is None


def test_nothing_is_read_where_the_ring_dropped_spans_in_the_window(later):
    lo, hi = later
    for i in range(obs.CAPACITY + 5):
        obs.record("loader.wait", lo + i % 100, lo + i % 100 + 1)
    assert obs.recorded() > len(obs.spans())
    assert _reader(FEED, "loader_wait_ms.feed")(_run(FEED, lo, hi), None, {}) is None
    # a later window, all of whose spans the ring still holds, is read
    lo2 = lo + 20_000
    obs.record("loader.wait", lo2 + 10, lo2 + 30)
    assert _reader(FEED, "loader_wait_ms.feed")(_run(FEED, lo2, lo2 + 100), None, {}) == \
        pytest.approx(20e-9 * 1e3)


# ---------------------------------------------------------- idle attribution
def _ev(name, start, end):
    return trace.Event(name, start, end)


def test_idle_gaps_are_attributed_to_the_feeders_state():
    # device busy [0,100) [300,400) [600,700) [900,1000) in a window [0,1000)
    devices = {"/device:TPU:0": [_ev("op", s, s + 100) for s in (0, 300, 600, 900)]}
    host = [_ev("device_loader.h2d", 120, 290),      # holds the gap [100,300)
            _ev("loader.wait", 390, 560),            # holds the gap [400,600)
            _ev("device_loader.dequant", 560, 640),  # not idle at its midpoints
            _ev("device_loader.put_wait", 800, 850)]  # holds [700,900)'s midpoint
    idle = program_spans.idle_by_state(devices, _ev(trace.WINDOW, 0, 1000), host)
    assert idle == {"device_loader.h2d": pytest.approx(200e-9),
                    "loader.wait": pytest.approx(200e-9),
                    "device_loader.put_wait": pytest.approx(200e-9)}
    host[3] = _ev("device_loader.put_wait", 710, 790)
    idle = program_spans.idle_by_state(devices, _ev(trace.WINDOW, 0, 1000), host)
    assert idle["outside spans"] == pytest.approx(200e-9)


def test_idle_in_h2d_share_reads_the_trace_on_the_programs_clock(later, monkeypatch):
    lo, hi = later
    t0 = lo - 1000  # the trace starts 1000 ns before the window
    obs.record("device_loader.h2d", lo + 100, lo + 300)
    obs.record("loader.wait", lo + 500, lo + 900)
    # device 0 on the trace's clock: busy [1000,1100) [1300,1500) [1900,2000)
    devices = {"/device:TPU:0": [_ev("op", s, e) for s, e in ((1000, 1100), (1300, 1500),
                                                               (1900, 2000))]}
    monkeypatch.setattr(program_spans.trace, "find", lambda d: d)
    monkeypatch.setattr(program_spans.trace, "read", lambda p: devices)
    run = _run(FEED, lo, lo + 1000, t0=t0, trace_dir="fabricated")
    share = _reader(FEED, "idle_in_h2d_share.feed")(run, None, {})
    # idle [1100,1300) in h2d (midpoint 1200 in [1100,1300)), [1500,1900) in loader.wait
    assert share == pytest.approx(100 * 200 / 600)
    assert run.counters["idle_s.loader.wait"] == pytest.approx(400e-9)


# ----------------------------------------------- one clock, at tiny size
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("program_spans")))


def _traced(root, cell, monkeypatch, tmp_path):
    """Run a tiny cell's generator as a traced run, with the profiler
    itself left off: what is checked is the host clock both sides share."""
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda *a, **k: None)
    run = harness.Run(cell=harness.resolve(cell, root), seed=7, seconds=0.5, trace=True,
                      t_process=time.perf_counter(), workdir=str(tmp_path))
    e2e = harness.load_module(run.cell.generator_file).run(run)
    assert run.correct, run.checks
    return run, e2e


def _inside(spans, bench_spans, slack_ns=2_000_000):
    return all(any(a - slack_ns <= s.t0 and s.t1 <= b + slack_ns for a, b in bench_spans)
               for s in spans)


def test_restore_spans_lie_inside_the_benchmarks_cold_starts(root, monkeypatch, tmp_path):
    run, e2e = _traced(root, "coldstart.tiny", monkeypatch, tmp_path)
    t = run._trace_t0
    boots = [(a + t, b + t) for n, a, b in run.spans if n == "coldstart.restore"]
    spans = program_spans.program_spans(run)
    mine = [s for s in spans if s.name in ("restore", "codec.fetch", "codec.inflate")]
    assert {s.name for s in mine} == {"restore", "codec.fetch", "codec.inflate"}
    assert _inside(mine, boots)
    stream = _reader(COLD, "stream_s.coldstart")(run, None, e2e)
    fetch = _reader(COLD, "fetch_thread_s.coldstart")(run, None, e2e)
    inflate = _reader(COLD, "inflate_thread_s.coldstart")(run, None, e2e)
    from repro.core import engine

    assert 0 < stream <= e2e["coldstart_s"]
    assert 0 < fetch + inflate <= engine.workers() * stream


def test_feed_spans_lie_inside_the_benchmarks_next(root, monkeypatch, tmp_path):
    run, e2e = _traced(root, "feed.tiny", monkeypatch, tmp_path)
    t = run._trace_t0
    nexts = [(a + t, b + t) for n, a, b in run.spans if n == "feed.next"]
    spans = program_spans.program_spans(run)
    waits = [s for s in spans if s.name == "device_loader.wait"]
    assert len(waits) >= len(nexts) - 1 > 0  # one per next(), clipped at the window's ends
    assert _inside(waits, nexts)
    for metric in ("gather_ms.feed", "loader_wait_ms.feed"):
        assert _reader(FEED, metric)(run, None, e2e) > 0, metric
