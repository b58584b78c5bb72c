"""Parallel I/O engine: slab math, chunk-boundary correctness, short-read
retries, coalescing equivalence, and byte-identity of engine paths vs the
seed sequential implementations."""

import os

import numpy as np
import pytest

import repro.core as ra
from repro.core import engine


# ------------------------------------------------------------- slab planner
def test_chunk_spans_cover_and_align():
    chunk = 1 << 12
    for offset, length in [(0, 10_000), (100, 10_000), (4095, 4097), (64, 1), (0, chunk)]:
        spans = engine.chunk_spans(offset, length, chunk)
        # exact cover, in order, no overlap
        pos = offset
        for off, ln in spans:
            assert off == pos and ln > 0
            pos += ln
        assert pos == offset + length
        # every interior boundary is chunk-aligned in absolute file offsets
        for off, _ in spans[1:]:
            assert off % chunk == 0


def test_chunk_spans_empty():
    assert engine.chunk_spans(123, 0, 1 << 12) == []


# -------------------------------------------------- reads across slab edges
@pytest.fixture()
def blob_file(tmp_path):
    data = np.random.default_rng(0).integers(0, 256, size=1 << 20, dtype=np.int64).astype(np.uint8)
    p = tmp_path / "blob.bin"
    p.write_bytes(data.tobytes())
    return str(p), data


def test_parallel_read_into_spanning_slabs(blob_file, monkeypatch):
    path, data = blob_file
    monkeypatch.setenv("RA_IO_CHUNK", str(1 << 14))     # 16 KiB slabs
    monkeypatch.setenv("RA_IO_PARALLEL_MIN", "1")       # force the parallel path
    fd = os.open(path, os.O_RDONLY)
    try:
        for offset, length in [(0, len(data)), (3, 1 << 15), ((1 << 14) - 1, 2), (5, 0)]:
            out = np.zeros(length, np.uint8)
            n = engine.parallel_read_into(fd, offset, memoryview(out))
            assert n == length
            assert np.array_equal(out, data[offset : offset + length])
    finally:
        os.close(fd)


def test_parallel_read_spans_multi_file(tmp_path, monkeypatch):
    monkeypatch.setenv("RA_IO_PARALLEL_MIN", "1")
    monkeypatch.setenv("RA_IO_CHUNK", str(1 << 13))
    rng = np.random.default_rng(1)
    parts = [rng.integers(0, 255, size=n, dtype=np.int64).astype(np.uint8) for n in (100, 1 << 15, 1)]
    fds = []
    try:
        for i, part in enumerate(parts):
            p = tmp_path / f"f{i}.bin"
            p.write_bytes(part.tobytes())
            fds.append(os.open(str(p), os.O_RDONLY))
        out = np.zeros(sum(len(p) for p in parts), np.uint8)
        mv = memoryview(out)
        jobs, pos = [], 0
        for fd, part in zip(fds, parts):
            jobs.append((fd, 0, mv[pos : pos + len(part)]))
            pos += len(part)
        engine.parallel_read_spans(jobs)
        assert np.array_equal(out, np.concatenate(parts))
    finally:
        for fd in fds:
            os.close(fd)


def test_short_reads_are_retried(blob_file, monkeypatch):
    """A pread returning fewer bytes than asked must loop, not truncate."""
    path, data = blob_file
    real = os.preadv

    def stingy(fd, bufs, offset):
        (buf,) = bufs
        return real(fd, [buf[: max(1, len(buf) // 3)]], offset)

    monkeypatch.setattr(engine, "_preadv", stingy)
    fd = os.open(path, os.O_RDONLY)
    try:
        out = np.zeros(10_000, np.uint8)
        engine.pread_into(fd, 77, memoryview(out))
        assert np.array_equal(out, data[77 : 77 + 10_000])
    finally:
        os.close(fd)


def test_read_past_eof_raises(blob_file):
    path, data = blob_file
    fd = os.open(path, os.O_RDONLY)
    try:
        out = np.zeros(100, np.uint8)
        with pytest.raises(ra.RawArrayError, match="truncated"):
            engine.pread_into(fd, len(data) - 50, memoryview(out))
    finally:
        os.close(fd)


def test_short_writes_are_retried(tmp_path, monkeypatch):
    real = os.pwritev

    def stingy(fd, bufs, offset):
        (buf,) = bufs
        return real(fd, [buf[: max(1, len(buf) // 4)]], offset)

    monkeypatch.setattr(engine, "_pwritev", stingy)
    payload = bytes(range(256)) * 40
    p = str(tmp_path / "w.bin")
    fd = os.open(p, os.O_WRONLY | os.O_CREAT, 0o644)
    try:
        engine.pwrite_from(fd, 0, memoryview(payload))
    finally:
        os.close(fd)
    assert open(p, "rb").read() == payload


# ------------------------------------------------------------ zero-length
def test_zero_length_everything(tmp_path):
    p = str(tmp_path / "z.ra")
    arr = np.empty((0, 5), np.float32)
    ra.write(p, arr)
    assert ra.read(p).shape == (0, 5)
    out = np.empty((0, 5), np.float32)
    assert ra.read_into(p, out) is out
    fd = os.open(p, os.O_RDONLY)
    try:
        assert engine.parallel_read_into(fd, 0, memoryview(b"")) == 0
    finally:
        os.close(fd)
    runs, leftover = engine.coalesce(np.empty(0, np.int64))
    assert runs == [] and leftover.size == 0


# ---------------------------------------------------------------- coalesce
@pytest.mark.parametrize("trial", range(12))
def test_coalesce_partitions_exactly(trial):
    rng = np.random.default_rng(trial)
    n = int(rng.integers(1, 400))
    indices = rng.integers(0, 1000, size=n)  # duplicates likely
    gap = int(rng.integers(0, 4))
    min_run = int(rng.integers(2, 6))
    runs, leftover = engine.coalesce(indices, gap=gap, min_run=min_run)
    cover = [leftover] + [r.sel for r in runs]
    allpos = np.sort(np.concatenate(cover))
    assert np.array_equal(allpos, np.arange(n))  # exact partition of positions
    for r in runs:
        vals = indices[r.sel]
        assert r.lo == vals.min() and r.hi == vals.max() + 1
        assert len(r.sel) >= min_run
        assert np.all(np.diff(np.sort(vals)) <= gap + 1)  # merged gaps bounded


def test_coalesce_adjacent_rows_merge():
    runs, leftover = engine.coalesce(np.array([7, 4, 5, 6]), gap=0, min_run=2)
    assert leftover.size == 0
    assert len(runs) == 1 and (runs[0].lo, runs[0].hi) == (4, 8)


# ----------------------------------------- gather / read_slice equivalence
@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    from repro.data import RaDataset, make_token_dataset

    root = str(tmp_path_factory.mktemp("eng") / "ds")
    make_token_dataset(root, n_docs=333, seq_len=24, vocab=97, shard_rows=100)
    return RaDataset(root)


def _wide_dataset(root, **kw):
    """Two plain fields whose rows are wider than the direct-copy threshold
    (u8 and float32) beside a 4-byte label, in four shards of 40 rows."""
    from repro.data import DatasetBuilder
    from repro.data.dataset import _DIRECT_ROW_BYTES

    img, emb = (80, 1024), (16640,)
    assert 80 * 1024 >= _DIRECT_ROW_BYTES and 4 * 16640 >= _DIRECT_ROW_BYTES
    rng = np.random.default_rng(5)
    b = DatasetBuilder(root, {"image": (img, "uint8"), "emb": (emb, "float32"),
                              "label": ((), "int32")}, shard_rows=40, **kw)
    b.append(image=rng.integers(0, 256, size=(160,) + img, dtype=np.uint8),
             emb=rng.normal(size=(160,) + emb).astype(np.float32),
             label=np.arange(160, dtype=np.int32))
    b.finish()
    return root


@pytest.fixture(scope="module")
def wide_dataset(tmp_path_factory):
    from repro.data import RaDataset

    return RaDataset(_wide_dataset(str(tmp_path_factory.mktemp("eng") / "wide")))


# the narrow token set (rows of 96 B and 4 B) keeps its seed ids; the wide
# set's rows are copied straight into the batch outside coalesced runs
GATHER_CASES = ([pytest.param("dataset", t, id=str(t)) for t in range(10)]
                + [pytest.param("wide_dataset", t, id=f"wide-{t}") for t in range(9)])


@pytest.mark.parametrize("which,trial", GATHER_CASES)
def test_gather_matches_naive_on_random_patterns(request, which, trial):
    dataset = request.getfixturevalue(which)
    rng = np.random.default_rng(100 + trial)
    n = int(rng.integers(1, 120))
    if trial % 3 == 0:       # dense-ish: exercises the coalesced ranged reads
        base = int(rng.integers(0, 200))
        idx = base + rng.integers(0, 40, size=n)
    elif trial % 3 == 1:     # sparse: exercises the mmap fancy fallback
        idx = rng.integers(0, len(dataset), size=n)
    else:                    # sorted contiguous with dups: exercises direct reads
        idx = np.sort(rng.integers(0, len(dataset), size=n))
    if which == "dataset":
        got = dataset.gather(idx)
        want = dataset.gather_naive(idx)
        assert set(got) == set(want)
        for f in got:
            assert np.array_equal(got[f], want[f]), f
        return
    # wide: shuffled across shards with duplicates, gathered in turn into
    # the slots of a preallocated ring as the loader's reused buffers are
    idx = idx % len(dataset)
    idx = rng.permutation(np.concatenate([idx, rng.choice(idx, size=len(idx) // 3 + 1)]))
    ring = [{f: np.full((len(idx),) + tuple(i["shape"]), 7, i["dtype"])
             for f, i in dataset.fields.items()} for _ in range(2)]
    for slot, batch in enumerate((idx, idx[::-1].copy())):
        got = dataset.gather(batch, out=ring[slot] if trial % 2 else None)
        want = dataset.gather_naive(batch)
        assert set(got) == set(want)
        for f in got:
            if trial % 2:
                assert got[f] is ring[slot][f]
            assert np.array_equal(got[f], want[f]), f


def _counted(ds, idx, fields=None):
    """``gather`` with the deltas of the two leftover counters."""
    from repro.core import obs

    d, f = obs.counter("dataset.direct_rows"), obs.counter("dataset.fancy_rows")
    d0, f0 = d.value, f.value
    got = ds.gather(idx, fields)
    return got, d.value - d0, f.value - f0


# every third row, shuffled and crossing all four shards: no coalesced run
SPARSE = np.random.default_rng(3).permutation(np.arange(0, 160, 3))


@pytest.mark.parametrize("field,direct", [("image", True), ("emb", True), ("label", False)])
def test_gather_counts_direct_rows_for_wide_plain_rows_only(wide_dataset, field, direct):
    got, nd, nf = _counted(wide_dataset, SPARSE, [field])
    assert (nd, nf) == ((len(SPARSE), 0) if direct else (0, len(SPARSE)))
    assert np.array_equal(got[field], wide_dataset.gather_naive(SPARSE, [field])[field])


def test_gather_reads_no_direct_rows_from_chunked_wide_fields(tmp_path, wide_dataset):
    from repro.data import RaDataset

    ds = RaDataset(_wide_dataset(str(tmp_path / "chunked"), chunked=True, codec="zlib"))
    got, nd, nf = _counted(ds, SPARSE)
    assert nd == 0 and nf == 0
    want = wide_dataset.gather_naive(SPARSE)
    for f in want:
        assert np.array_equal(got[f], want[f]), f
    ds.close()


def test_gather_into_preallocated_out(dataset):
    idx = np.array([3, 4, 5, 6, 250, 11, 12, 13, 14, 3])
    out = {
        f: np.empty((len(idx),) + tuple(i["shape"]), i["dtype"])
        for f, i in dataset.fields.items()
    }
    got = dataset.gather(idx, out=out)
    for f in out:
        assert got[f] is out[f]
        assert np.array_equal(got[f], dataset.gather_naive(idx)[f])


def test_rows_matches_gather_naive(dataset):
    got = dataset.rows(90, 210)  # spans two shard boundaries (100, 200)
    want = dataset.gather_naive(np.arange(90, 210))
    for f in got:
        assert np.array_equal(got[f], want[f])


@pytest.mark.parametrize("nshards", [1, 3, 7])
def test_read_slice_matches_naive_and_sharded(tmp_path, nshards):
    arr = np.random.default_rng(nshards).normal(size=(101, 6)).astype(np.float32)
    d = str(tmp_path / f"s{nshards}")
    ra.write_sharded(d, arr, nshards=nshards)
    assert np.array_equal(ra.read_sharded(d), arr)
    for lo, hi in [(0, 101), (13, 87), (50, 51), (40, 40), (-5, 400)]:
        got = ra.read_slice(d, lo, hi)
        naive = ra.read_slice_naive(d, lo, hi)
        assert np.array_equal(got, naive)
        assert np.array_equal(got, arr[max(lo, 0) : min(hi, 101)])


def test_read_slice_empty_respects_axis(tmp_path):
    arr = np.arange(60, dtype=np.int32).reshape(12, 5)
    d = str(tmp_path / "ax1")
    ra.write_sharded(d, arr, nshards=2, axis=1)
    empty = ra.read_slice(d, 3, 3)
    assert empty.shape == (12, 0)  # axis=1 empty slice keeps the other dims
    assert np.array_equal(ra.read_sharded(d), arr)


def test_read_slice_into_out(tmp_path):
    arr = np.random.default_rng(3).integers(0, 1000, size=(64, 9)).astype(np.int64)
    d = str(tmp_path / "out")
    ra.write_sharded(d, arr, nshards=5)
    out = np.full((30, 9), -1, np.int64)
    got = ra.read_slice(d, 10, 40, out=out)
    assert got is out
    assert np.array_equal(out, arr[10:40])
    with pytest.raises(ra.RawArrayError, match="out"):
        ra.read_slice(d, 10, 40, out=np.empty((3, 9), np.int64))


# ------------------------------------------------------------ byte identity
def test_parallel_write_bytes_identical_to_sequential(tmp_path, monkeypatch):
    arr = np.random.default_rng(5).normal(size=(1 << 18,)).astype(np.float32)  # 1 MiB
    p_seq, p_par = str(tmp_path / "s.ra"), str(tmp_path / "p.ra")
    monkeypatch.setenv("RA_IO_SEQUENTIAL", "1")
    ra.write(p_seq, arr, metadata=b"tail")
    monkeypatch.delenv("RA_IO_SEQUENTIAL")
    monkeypatch.setenv("RA_IO_PARALLEL_MIN", "1")
    monkeypatch.setenv("RA_IO_CHUNK", str(1 << 16))
    ra.write(p_par, arr, metadata=b"tail")
    assert open(p_seq, "rb").read() == open(p_par, "rb").read()


def test_parallel_read_identical_to_sequential(tmp_path, monkeypatch):
    arr = np.random.default_rng(6).normal(size=(300, 1000)).astype(np.float64)
    p = str(tmp_path / "x.ra")
    ra.write(p, arr)
    monkeypatch.setenv("RA_IO_SEQUENTIAL", "1")
    seq = ra.read(p)
    monkeypatch.delenv("RA_IO_SEQUENTIAL")
    monkeypatch.setenv("RA_IO_PARALLEL_MIN", "1")
    monkeypatch.setenv("RA_IO_CHUNK", str(1 << 16))
    par = ra.read(p)
    assert np.array_equal(seq, par) and seq.dtype == par.dtype


# ------------------------------------------------------------- read_into
def test_read_into_validates(tmp_path):
    p = str(tmp_path / "v.ra")
    arr = np.arange(24, dtype=np.float32).reshape(4, 6)
    ra.write(p, arr)
    with pytest.raises(ra.RawArrayError, match="shape"):
        ra.read_into(p, np.empty((6, 4), np.float32))
    with pytest.raises(ra.RawArrayError, match="dtype"):
        ra.read_into(p, np.empty((4, 6), np.float64))
    with pytest.raises(ra.RawArrayError, match="contiguous"):
        ra.read_into(p, np.empty((4, 12), np.float32)[:, ::2])
    out = np.empty((4, 6), np.float32)
    assert np.array_equal(ra.read_into(p, out), arr)


def test_read_into_compressed_fallback(tmp_path):
    p = str(tmp_path / "c.ra")
    arr = np.arange(1000, dtype=np.int32)
    ra.write(p, arr, compress=True)
    out = np.empty(1000, np.int32)
    assert np.array_equal(ra.read_into(p, out), arr)


def test_read_into_big_endian_fallback(tmp_path):
    """A native-endian destination must accept a big-endian payload via the
    read() fallback (the dtype check is byte-order-insensitive)."""
    p = str(tmp_path / "be.ra")
    arr = np.arange(100, dtype=np.float32)
    ra.write(p, arr, big_endian=True)
    out = np.empty(100, np.float32)
    assert np.array_equal(ra.read_into(p, out), arr)
