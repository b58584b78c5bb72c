"""Spans and counters (``repro.core.obs``, DESIGN.md §18): the recorder
itself, and the spans the cold-start restore and the device feed record,
checked against the counters and timers they replaced."""

import glob
import os
import sys
import threading

import numpy as np
import pytest

import repro.core as ra
from repro import remote
from repro.checkpoint import ColdStartStats, restore_pipelined, save_checkpoint
from repro.core import codec, obs
from repro.data import DataLoader, DatasetBuilder, DeviceLoader, RaDataset


def _since(t0, *names):
    """Spans recorded since ``t0`` (``obs.now()``), by name."""
    return [s for s in obs.spans() if s.t0 >= t0 and (not names or s.name in names)]


def _ancestors(span, by_id):
    out = []
    while span.parent in by_id:
        span = by_id[span.parent]
        out.append(span)
    return out


# ------------------------------------------------------------- the recorder
def _nest_on_thread():
    with obs.span("t.outer") as outer:
        with obs.span("t.inner", 7) as inner:
            pass
    return outer, inner


def _nest_under_parent():
    pid = obs.new_id()
    with obs.under(pid):
        with obs.span("t.inner", 7) as inner:
            pass
    return pid, inner


@pytest.mark.parametrize("how", ["with", "under"])
def test_nested_spans_name_their_parent(how):
    t0 = obs.now()
    if how == "with":
        outer, inner = _nest_on_thread()
        pid = outer.id
        assert obs.current() is None
        (rec_outer,) = _since(t0, "t.outer")
        assert rec_outer.parent is None and rec_outer.t0 <= inner.t0 <= inner.t1 <= rec_outer.t1
    else:
        pid, inner = _nest_under_parent()
    (rec,) = _since(t0, "t.inner")
    assert rec.parent == pid and rec.id == inner.id and rec.n == 7
    assert rec.thread == threading.get_ident()
    assert rec.seconds == inner.seconds >= 0


def test_many_threads_lose_no_span_below_capacity():
    threads, per = 8, 8_000
    assert threads * per < obs.CAPACITY
    start = obs.recorded()
    t0 = obs.now()
    barrier = threading.Barrier(threads)

    def work():
        barrier.wait()
        for i in range(per):
            with obs.span("t.stress", i):
                pass

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(prev)
    assert obs.recorded() - start == threads * per
    kept = _since(t0, "t.stress")
    # every span is kept, each thread's in its own order, and no id repeats
    assert len(kept) == threads * per
    assert len({s.id for s in kept}) == len(kept)
    by_thread = {}
    for s in kept:
        by_thread.setdefault(s.thread, []).append(s.n)
    assert len(by_thread) == threads
    for ns in by_thread.values():
        assert ns == list(range(per))


def test_ring_drops_the_oldest_past_capacity_and_recorded_shows_it():
    start = obs.recorded()
    t0 = obs.now()
    extra = 10
    for i in range(obs.CAPACITY + extra):
        obs.record("t.fill", t0 + i, t0 + i + 1, n=i)
    assert obs.recorded() - start == obs.CAPACITY + extra
    assert obs.recorded() == obs.recorded()  # reading the count leaves it
    kept = obs.spans()
    assert len(kept) == obs.CAPACITY
    assert [s.n for s in kept if s.name == "t.fill"] == list(range(extra, obs.CAPACITY + extra))


def test_record_spans_threads_and_clips_to_a_window():
    t0 = obs.now()
    with obs.span("t.parent") as parent:
        started = obs.now()
    out = []
    t = threading.Thread(target=lambda: out.append(
        obs.record("t.cross", started, obs.now(), parent=parent.id, n=3)))
    t.start()
    t.join(timeout=10)
    (rec,) = _since(t0, "t.cross")
    assert rec == out[0] and rec.parent == parent.id and rec.n == 3
    assert rec.thread == t.ident != threading.get_ident()
    lo, hi = rec.t0 + 1, rec.t1 - 1
    (clipped,) = [s for s in obs.spans(lo, hi) if s.id == rec.id]
    assert (clipped.t0, clipped.t1) == (lo, hi)
    assert not [s for s in obs.spans(rec.t1, rec.t1 + 1) if s.id == rec.id]


def test_counters_add_across_threads_and_codec_stats_read_them():
    c = obs.counter("t.adds")
    c.reset()
    assert obs.counter("t.adds") is c

    def work():
        for _ in range(1000):
            c.add(2)

    ts = [threading.Thread(target=work) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert c.value == 16_000
    obs.counter("codec.chunk_reads").add(3)
    assert codec.stats()["chunk_reads"] >= 3
    codec.reset_stats()
    assert codec.stats() == {"chunk_reads": 0, "chunk_stored_bytes": 0, "chunk_raw_bytes": 0}


# ------------------------------------------------------- cold-start restore
@pytest.fixture()
def served_checkpoint(tmp_path):
    rng = np.random.default_rng(0)
    tree = {"w": rng.standard_normal((96, 64)).astype(np.float32),
            "inner": {"b": rng.standard_normal((64,)).astype(np.float32),
                      "k": rng.standard_normal((32, 48)).astype(np.float32)}}
    path = save_checkpoint(str(tmp_path), 1, tree, chunked=True, quantize="u8", chunk_bytes=1024)
    chunks = 0
    for f in glob.glob(os.path.join(path, "*.ra")):
        hdr = ra.header_of(f)
        fd = os.open(f, os.O_RDONLY)
        try:
            chunks += codec.read_table(fd, hdr).nchunks
        finally:
            os.close(fd)
    server = remote.serve(str(tmp_path), port=0)
    try:
        yield tree, f"{server.url}/{os.path.basename(path)}", chunks
    finally:
        server.shutdown()
        server.server_close()
        remote.close_readers()
        remote.reset_shared_cache()


def test_restore_spans_match_the_chunks_and_the_h2d_timer(served_checkpoint):
    import jax

    tree, url, chunks = served_checkpoint
    like = jax.tree_util.tree_map(lambda x: np.empty(x.shape, x.dtype), tree)
    assert chunks > 3  # several chunks per leaf
    remote.close_readers()
    remote.reset_shared_cache()
    reads = codec.stats()["chunk_reads"]
    t0 = obs.now()
    st = ColdStartStats()
    restore_pipelined(url, like, stats=st)
    spans = _since(t0)
    by_id = {s.id: s for s in spans}
    (restore,) = [s for s in spans if s.name == "restore"]
    fetches = [s for s in spans if s.name == "codec.fetch"]
    assert len(fetches) == chunks == codec.stats()["chunk_reads"] - reads
    for s in fetches + [s for s in spans if s.name == "codec.inflate"]:
        names = [(a.name, a.parent) for a in _ancestors(s, by_id)]
        assert ("restore.leaf", restore.id) in names, s
    leaves = [s for s in spans if s.name == "restore.leaf"]
    assert len(leaves) == st.leaves == 3
    assert sum(s.n for s in leaves) == st.stored_bytes
    h2d = sum(s.seconds for s in spans if s.name in ("restore.h2d", "restore.drain"))
    assert st.h2d_s == pytest.approx(h2d, abs=1e-6)
    assert st.restore_s == restore.seconds
    (pin,) = [s for s in spans if s.name == "restore.pin"]
    assert pin.parent == restore.id and st.resolve_s == pin.seconds


# ---------------------------------------------------------------- the feed
@pytest.fixture(scope="module")
def image_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("obs") / "imgs")
    rng = np.random.default_rng(0)
    b = DatasetBuilder(root, {"image": ((6, 6, 3), "float32"), "label": ((), "int32")},
                       shard_rows=96, quantize={"image": "u8"})
    b.append(image=rng.random((250, 6, 6, 3)).astype(np.float32),
             label=rng.integers(0, 10, 250).astype(np.int32))
    b.finish()
    return root


@pytest.mark.parametrize("shuffle,read", [(True, "dataset.gather"), (False, "dataset.rows")])
def test_feed_spans_match_the_loader_timers(image_root, shuffle, read):
    t0 = obs.now()
    dl = DeviceLoader(DataLoader(RaDataset(image_root), 16, seed=3, shuffle=shuffle),
                      interpret=True)
    try:
        delivered = 6
        for _ in range(delivered):
            next(dl)
        producer, feeder = dl.loader._thread.ident, dl._thread.ident
    finally:
        dl.stop()
    stats = dl.stats()
    spans = _since(t0)

    def of(name, thread):
        return [s for s in spans if s.name == name and s.thread == thread]

    produced, h2d = of("loader.produce", producer), of("device_loader.h2d", feeder)
    # one produce per batch the loader made (prefetch runs ahead of the
    # feeder), one H2D per batch the feeder moved
    assert delivered <= stats["h2d_batches"] == len(h2d) <= len(produced)
    assert len(produced) <= stats["h2d_batches"] + dl.loader.prefetch + 2
    assert all(s.n == 16 for s in produced)
    assert all(s.n == 16 * (6 * 6 * 3 + 4) for s in h2d)
    assert stats["loader_produce_s"] == pytest.approx(sum(s.seconds for s in produced), abs=1e-9)
    assert stats["h2d_s"] == pytest.approx(sum(s.seconds for s in h2d), abs=1e-9)
    waits = of("loader.wait", feeder)
    assert len(waits) == stats["batches"]
    assert stats["loader_wait_s"] == pytest.approx(sum(s.seconds for s in waits), abs=1e-9)
    dwaits = of("device_loader.wait", threading.get_ident())
    assert len(dwaits) == stats["device_batches"] == delivered
    assert stats["device_wait_s"] == pytest.approx(sum(s.seconds for s in dwaits), abs=1e-9)
    gathers = of(read, producer)
    assert len(gathers) == len(produced)
    assert all(g.parent in {p.id for p in produced} for g in gathers)
