"""Device feed plane (DESIGN.md §12): DeviceLoader↔DataLoader equivalence,
u8 quantize/dequant roundtrips (interpret-mode Pallas on CPU), and the
loader-lifecycle fixes — sticky producer errors and the stop()/restore()
zombie-ring race."""

import os
import threading
import time

import numpy as np
import pytest

import repro.core as ra
from repro.core import obs
from repro.data import (
    DataLoader,
    DatasetBuilder,
    DeviceLoader,
    LoaderState,
    RaDataset,
    make_token_dataset,
)


@pytest.fixture(scope="module")
def token_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("dfeed") / "toks")
    make_token_dataset(root, n_docs=256, seq_len=16, vocab=64, shard_rows=100)
    return root


@pytest.fixture(scope="module")
def quant_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("dfeed") / "imgs")
    rng = np.random.default_rng(0)
    b = DatasetBuilder(
        root,
        {"image": ((6, 6, 3), "float32"), "label": ((), "int32")},
        shard_rows=96,
        quantize={"image": "u8"},
    )
    b.append(
        image=rng.random((250, 6, 6, 3)).astype(np.float32),
        label=rng.integers(0, 10, 250).astype(np.int32),
    )
    b.finish()
    return root


# ------------------------------------------------ bugfix: sticky producer error
def test_dead_producer_error_is_sticky(token_root):
    """The prefetch thread puts ONE exception and exits; before the fix the
    second next() blocked forever on the empty queue. Now every subsequent
    next() re-raises."""
    dl = DataLoader(RaDataset(token_root), 16, seed=0)
    boom = RuntimeError("disk on fire")

    def bad_produce(epoch, step, out=None):
        raise boom

    dl._produce = bad_produce
    with pytest.raises(RuntimeError, match="disk on fire"):
        next(dl)
    # the regression hung here forever — any completion at all is the fix,
    # and it must be the SAME error, immediately
    t0 = time.perf_counter()
    for _ in range(3):
        with pytest.raises(RuntimeError, match="disk on fire"):
            next(dl)
    assert time.perf_counter() - t0 < 1.0
    dl.stop()


def test_error_cleared_by_stop_then_restart(token_root):
    dl = DataLoader(RaDataset(token_root), 16, seed=0)
    orig = DataLoader._produce.__get__(dl)
    dl._produce = lambda e, s, out=None: (_ for _ in ()).throw(ValueError("x"))
    with pytest.raises(ValueError):
        next(dl)
    dl.stop()
    del dl._produce  # restore the class implementation
    assert "tokens" in next(dl)
    dl.stop()
    assert orig is not None


def test_device_loader_error_is_sticky(token_root):
    dl = DataLoader(RaDataset(token_root), 16, seed=0)
    dl._produce = lambda e, s, out=None: (_ for _ in ()).throw(OSError("gone"))
    dev = DeviceLoader(dl)
    with pytest.raises(OSError, match="gone"):
        next(dev)
    with pytest.raises(OSError, match="gone"):
        next(dev)  # sticky through the device pipeline too
    dev.stop()


# --------------------------------------- bugfix: stop()/restore() zombie ring
def test_stop_verifies_join_and_discards_ring(token_root):
    """A producer wedged past the join timeout must not leave its ring to a
    successor: stop() discards the buffers so the restarted loader can
    never alias batches with the zombie."""
    dl = DataLoader(RaDataset(token_root), 16, seed=1, reuse_buffers=True,
                    prefetch=1)
    gate = threading.Event()
    entered = threading.Event()
    orig = DataLoader._produce.__get__(dl)

    def wedged(epoch, step, out=None):
        entered.set()
        gate.wait()
        return orig(epoch, step, out)

    dl._produce = wedged
    dl._start_prefetch()
    assert entered.wait(5.0)
    old_ring = dl._ring
    zombie = dl._thread
    assert old_ring  # allocated by _start_prefetch
    dl.stop(join_timeout=0.2)  # zombie ignores the stop: join must time out
    assert zombie.is_alive()
    assert dl._ring == []  # the ring went with it

    # restart: fresh ring, and the zombie's eventual write lands in the
    # orphaned buffers, not in anything the new loader emits (compare one
    # batch at a time — emitted batches alias the live ring by contract)
    del dl._produce
    ref = DataLoader(RaDataset(token_root), 16, seed=1)
    for i in range(6):
        if i == 3:
            gate.set()  # let the zombie finish its produce mid-iteration
        b, r = next(dl), next(ref)
        assert np.array_equal(b["tokens"], r["tokens"]), i
        assert b["_state"].__dict__ == r["_state"].__dict__
    assert dl._ring and dl._ring is not old_ring
    zombie.join(timeout=5.0)
    assert not zombie.is_alive()  # its private stop event was left set
    dl.stop()
    ref.stop()


def test_clean_stop_keeps_ring(token_root):
    dl = DataLoader(RaDataset(token_root), 16, seed=2, reuse_buffers=True)
    next(dl)
    ring = dl._ring
    assert ring
    dl.stop()
    assert dl._ring is ring  # healthy join: buffers are reusable


def test_restore_after_wedged_stop_is_exact(token_root):
    """restore() goes through stop(): even with a wedged producer the
    resumed sequence is exactly the reference sequence."""
    ref = DataLoader(RaDataset(token_root), 16, seed=3)
    batches = [next(ref) for _ in range(5)]
    ref.stop()

    dl = DataLoader(RaDataset(token_root), 16, seed=3, reuse_buffers=True)
    [next(dl) for _ in range(3)]
    gate = threading.Event()
    orig = DataLoader._produce.__get__(dl)
    dl._produce = lambda e, s, out=None: (gate.wait(), orig(e, s, out))[1]
    time.sleep(0.05)  # let the producer enter the wedge
    dl.restore(batches[2]["_state"])  # join may time out; ring discarded
    del dl._produce
    gate.set()
    nxt = next(dl)
    assert nxt["_state"].__dict__ == batches[3]["_state"].__dict__
    assert np.array_equal(nxt["tokens"], batches[3]["tokens"])
    dl.stop()


# ------------------------------------------------- quantize/dequant roundtrip
def test_write_quantize_u8_roundtrip(tmp_path):
    x = np.random.default_rng(0).normal(size=(64, 5)).astype(np.float32)
    p = str(tmp_path / "q.ra")
    ra.write(p, x, quantize="u8")
    hdr = ra.header_of(p)
    assert hdr.dtype() == np.uint8 and hdr.shape == (64, 5)
    info = ra.read_quant_metadata(p)
    assert info is not None and info.mode == "u8"
    assert info.orig_dtype == "float32" and info.scale.shape == (5,)
    y = ra.read(p, dequantize=True)
    assert y.dtype == np.float32
    # affine u8: error bounded by half a step per channel
    assert (np.abs(y - x) <= info.scale / 2 + 1e-6).all()
    # without dequantize the codes come back raw
    assert ra.read(p).dtype == np.uint8


def test_write_quantize_exact_on_u8_grid(tmp_path):
    """Values already on the u8 grid of the calibrated range roundtrip
    EXACTLY (the image-pixel case). Pin the per-channel calibration to
    [0, 255] by including both extremes in every channel."""
    codes = np.random.default_rng(1).integers(0, 256, (32, 4), dtype=np.uint8)
    codes[0] = 0
    codes[1] = 255
    x = codes.astype(np.float32)  # range [0, 255], step 1 -> scale exactly 1
    p = str(tmp_path / "g.ra")
    ra.write(p, x, quantize="u8")
    info = ra.read_quant_metadata(p)
    assert np.array_equal(info.scale, np.ones(4, np.float32))
    assert np.array_equal(ra.read(p, dequantize=True), x)


def test_quantize_merges_user_metadata(tmp_path):
    p = str(tmp_path / "m.ra")
    ra.write(p, np.ones((4, 2), np.float32), quantize="u8",
             metadata=b'{"units": "mm"}')
    import json

    meta = json.loads(ra.read_metadata(p))
    assert meta["units"] == "mm" and "ra_quant" in meta
    with pytest.raises(ra.RawArrayError, match="JSON object"):
        ra.write(p, np.ones((4, 2), np.float32), quantize="u8", metadata=b"\xff\x00")


def test_quantize_rejects_bad_inputs(tmp_path):
    with pytest.raises(ra.RawArrayError, match="float"):
        ra.write(str(tmp_path / "i.ra"), np.ones((4,), np.int32), quantize="u8")
    with pytest.raises(ra.RawArrayError, match="0-d"):
        ra.write(str(tmp_path / "z.ra"), np.float32(1.0), quantize="u8")
    with pytest.raises(ra.RawArrayError, match="unknown quantization mode"):
        ra.quant_params(np.ones((4,), np.float32), mode="u4")


def test_builder_quantize_validation(tmp_path):
    fields = {"x": ((4,), "float32"), "lab": ((), "int32")}
    with pytest.raises(ra.RawArrayError, match="unknown field"):
        DatasetBuilder(str(tmp_path / "a"), fields, quantize={"nope": "u8"})
    with pytest.raises(ra.RawArrayError, match="float"):
        DatasetBuilder(str(tmp_path / "b"), fields, quantize={"lab": "u8"})
    with pytest.raises(ra.RawArrayError, match="scalar row shape"):
        DatasetBuilder(str(tmp_path / "c"), {"s": ((), "float32")},
                       quantize={"s": "u8"})
    with pytest.raises(ra.RawArrayError, match="hi > lo"):
        ra.resolve_quant_spec(("u8", 2.0, 1.0))


def test_quantized_dataset_schema_and_shards(quant_root):
    ds = RaDataset(quant_root)
    assert set(ds.quant) == {"image"}
    assert ds.stored_spec("image") == ((6, 6, 3), np.dtype(np.uint8))
    assert ds.logical_spec("image") == ((6, 6, 3), np.dtype(np.float32))
    assert ds.fields["image"]["dtype"] == "float32"  # manifest stays logical
    # raw reads serve stored codes
    assert ds.rows(0, 8)["image"].dtype == np.uint8
    # every shard file is self-describing: header uint8 + typed metadata
    shard = os.path.join(quant_root, ds.shards[0].files["image"])
    assert ra.header_of(shard).dtype() == np.uint8
    sinfo = ra.read_quant_metadata(shard)
    assert sinfo is not None and sinfo.to_dict() == ds.quant["image"].to_dict()


def test_host_loader_dequantizes_by_default(quant_root):
    ds = RaDataset(quant_root)
    dl = DataLoader(ds, 16, seed=4, shuffle=False)
    b = next(dl)
    dl.stop()
    assert b["image"].dtype == np.float32
    manual = ds.quant["image"].dequantize(ds.rows(0, 16)["image"])
    assert np.array_equal(b["image"], manual)
    raw = DataLoader(ds, 16, seed=4, shuffle=False, dequant=False)
    assert next(raw)["image"].dtype == np.uint8
    raw.stop()


# --------------------------------------------- DeviceLoader batch equivalence
def _equiv(root, *, batch=16, seed=7):
    """``DeviceLoader`` turns on its wrapped loader's ring. Every batch it
    delivers is held on the device through three laps of that ring and only
    then compared with the host loader's, so a put that aliased a ring slot
    (the CPU backend's can) would show as a changed batch. The ring takes
    one fill a batch produced; on the CPU every block is copied before its
    put."""
    fills = obs.counter("loader.ring_fills")
    detached = obs.counter("device_loader.detached")
    inner = DataLoader(RaDataset(root), batch, seed=seed)
    produce, into_ring = inner._produce, []

    def produce_counted(epoch, step, out=None):
        into_ring.append(out is not None)
        return produce(epoch, step, out)

    inner._produce = produce_counted
    dev = DeviceLoader(inner)
    assert inner.reuse_buffers
    host = DataLoader(RaDataset(root), batch, seed=seed)
    f0, d0 = fills.value, detached.value
    try:
        held = [next(dev) for _ in range(3 * (inner._qcap + 2))]
        want = [next(host) for _ in held]
    finally:
        host.stop()
        dev.stop()
    for hb, db in zip(want, held):
        assert hb["_state"].__dict__ == db["_state"].__dict__
        for f in hb:
            if f == "_state":
                continue
            da = np.asarray(db[f])
            assert da.dtype == hb[f].dtype
            assert np.array_equal(da, hb[f]), f
    assert all(into_ring) and fills.value - f0 == len(into_ring) >= len(held)
    assert detached.value - d0 == dev.stats()["h2d_batches"] >= len(held)


def test_device_loader_matches_host_tokens(token_root):
    _equiv(token_root)


def test_device_loader_matches_host_quantized(quant_root):
    """uint8 over the 'link' + interpret-mode Pallas dequant on CPU is
    bit-identical to the host numpy dequant (same float32 affine)."""
    _equiv(quant_root)


def test_device_loader_leaves_the_naive_loader_without_a_ring(token_root):
    """The seed-era ``naive`` path has no ring, so nothing is copied before
    a put either."""
    detached = obs.counter("device_loader.detached")
    inner = DataLoader(RaDataset(token_root), 16, seed=7, naive=True)
    dev = DeviceLoader(inner)
    ref = DataLoader(RaDataset(token_root), 16, seed=7)
    d0 = detached.value
    try:
        for _ in range(3):
            assert np.array_equal(np.asarray(next(dev)["tokens"]), next(ref)["tokens"])
    finally:
        dev.stop()
        ref.stop()
    assert not inner.reuse_buffers and not inner._ring
    assert detached.value == d0


@pytest.mark.parametrize("platforms,copies", [
    (("cpu",), True), (("tpu",), False), (("gpu",), False),
    (("tpu",) * 4, False), (("tpu", "cpu"), True),
])
def test_detach_rule_follows_the_target_platform(platforms, copies):
    """A block is copied before its put only where a target's put may alias
    host memory: any CPU device among the targets."""
    from types import SimpleNamespace

    from repro.data.device_loader import may_alias_host

    assert may_alias_host([SimpleNamespace(platform=p) for p in platforms]) is copies


def test_device_loader_moves_quantized_bytes(quant_root):
    dev = DeviceLoader(DataLoader(RaDataset(quant_root), 16, seed=0))
    next(dev)
    s = dev.stats()
    dev.stop()
    per_batch = s["h2d_bytes"] / s["h2d_batches"]
    # image moves as u8 codes (108 B/row) + int32 label: 4x less than f32
    assert per_batch == 16 * (6 * 6 * 3 + 4)
    assert {"h2d_s", "device_wait_s", "device_batches"} <= set(s)


# ------------------------------- narrow minor axis: the kernel's packed view
# Per-channel scales of few significant bits make every q*scale exact in
# float32, so the host's multiply-then-add and a backend's fused
# multiply-add round alike and the comparison can be bitwise: what it
# guards is the packed layout (channel and row order), where a fault
# shows as a whole wrong value.
_PACK_CASES = {
    # 8 rows x 16x16x3 = 6144 codes = 16 rows of lcm(3, 128) = 384 lanes
    "packed": ((16, 16, 3), 1),
    # 8 rows x 5x5x3 = 600 codes: not a multiple of 384, crosses as it is
    "fallback": ((5, 5, 3), 0),
    # a minor axis of 128 is whole lanes already
    "wide": ((4, 128), 0),
}


@pytest.fixture(scope="module")
def pack_roots(tmp_path_factory):
    base = tmp_path_factory.mktemp("pack")
    rng = np.random.default_rng(5)
    roots = {}
    for name, (shape, _) in _PACK_CASES.items():
        c = shape[-1]
        scale = (np.arange(c, dtype=np.float32) % 7 + 3) / 1024
        bias = np.linspace(-2.1, 1.3, c).astype(np.float32)
        roots[name] = root = str(base / name)
        b = DatasetBuilder(
            root, {"image": (shape, "float32"), "label": ((), "int32")},
            shard_rows=24,
            quantize={"image": ra.QuantInfo(scale=scale, bias=bias, orig_dtype="float32")},
        )
        codes = rng.integers(0, 256, (72,) + shape).astype(np.float32)
        b.append(image=codes * scale + bias, label=np.arange(72, dtype=np.int32))
        b.finish()
    return roots


@pytest.mark.parametrize("reuse", [False, True])
@pytest.mark.parametrize("case", list(_PACK_CASES))
def test_device_loader_packed_view_matches_host(pack_roots, case, reuse):
    """A u8 image field whose batch fills whole ``(n, 384)`` rows crosses
    packed and decodes to the host's dequantization bit for bit, in its
    logical shape; one that does not, or whose minor axis is 128 wide,
    crosses as it is. ``device_loader.packed`` counts one per batch put
    in the first case and none in the others. The feed stages its host
    batches in the ring whatever the wrapped loader was given."""
    shape, per_batch = _PACK_CASES[case]
    root = pack_roots[case]
    packed = obs.counter("device_loader.packed")
    host = DataLoader(RaDataset(root), 8, seed=11)
    dev = DeviceLoader(DataLoader(RaDataset(root), 8, seed=11, reuse_buffers=reuse))
    assert dev.loader.reuse_buffers
    before = packed.value
    try:
        for _ in range(5):
            hb, db = next(host), next(dev)
            assert hb["_state"].__dict__ == db["_state"].__dict__
            for f in ("image", "label"):
                da = np.asarray(db[f])
                assert da.shape == hb[f].shape and da.dtype == hb[f].dtype, f
                assert np.array_equal(da, hb[f]), f
            assert db["image"].shape == (8,) + shape
    finally:
        host.stop()
        dev.stop()
    puts = dev.stats()["h2d_batches"]
    assert puts >= 5
    assert packed.value - before == per_batch * puts


def test_packed_width_is_the_kernels_repacking_rule():
    from repro.kernels.dequant_u8 import packed_width

    assert packed_width((256, 224, 224, 3)) == 384      # (100352, 384)
    assert packed_width((8, 16, 16, 3)) == 384
    assert packed_width((8, 5, 5, 3)) == 0               # 600 codes
    assert packed_width((8, 4, 128)) == 0                # whole lanes
    assert packed_width((8, 256)) == 0
    assert packed_width((4, 8, 8, 3)) == 384             # one device's block
    assert packed_width((16, 96)) == 384                 # lcm(96, 128)
    assert packed_width((0, 16, 16, 3)) == 0             # empty block


def test_device_loader_restore_exact(token_root):
    ref = DataLoader(RaDataset(token_root), 16, seed=9)
    batches = [next(ref) for _ in range(5)]
    ref.stop()
    dev = DeviceLoader(DataLoader(RaDataset(token_root), 16, seed=9))
    [next(dev) for _ in range(2)]
    dev.restore(batches[2]["_state"])
    nxt = next(dev)
    dev.stop()
    assert nxt["_state"].__dict__ == batches[3]["_state"].__dict__
    assert np.array_equal(np.asarray(nxt["tokens"]), batches[3]["tokens"])


def test_device_loader_refuses_started_loader(token_root):
    dl = DataLoader(RaDataset(token_root), 16, seed=0)
    next(dl)
    with pytest.raises(ra.RawArrayError, match="not started"):
        DeviceLoader(dl)
    dl.stop()
    DeviceLoader(dl).stop()  # after stop() wrapping is fine


def test_device_bufs_knob(token_root, monkeypatch):
    monkeypatch.setenv("RA_DEVICE_BUFS", "5")
    dev = DeviceLoader(DataLoader(RaDataset(token_root), 16, seed=0))
    assert dev.bufs == 5
    dev.stop()
    dev2 = DeviceLoader(DataLoader(RaDataset(token_root), 16, seed=0), bufs=1)
    assert dev2.bufs == 1
    dev2.stop()


# ----------------------------------------------- review-hardening regressions
def test_quantize_1d_uses_scalar_params(tmp_path):
    """A 1-D array is ONE channel: calibration must be a global scalar, not
    one (scale, bias) pair per element (metadata bigger than the payload)."""
    x = np.random.default_rng(3).normal(size=4096).astype(np.float32)
    p = str(tmp_path / "one_d.ra")
    ra.write(p, x, quantize="u8")
    info = ra.read_quant_metadata(p)
    assert info.scale.ndim == 0 and info.bias.ndim == 0
    assert len(ra.read_metadata(p)) < 256
    y = ra.read(p, dequantize=True)
    assert (np.abs(y - x) <= float(info.scale) / 2 + 1e-6).all()


def test_channel_params_mismatch_raises_rawarray_error():
    info = ra.QuantInfo(scale=np.ones(3, np.float32), bias=np.zeros(3, np.float32))
    with pytest.raises(ra.RawArrayError, match="3 entries.*5 channels"):
        info.channel_params(5)
    bad_bias = ra.QuantInfo(scale=np.float32(1.0), bias=np.zeros(2, np.float32))
    with pytest.raises(ra.RawArrayError, match="bias has 2 entries"):
        bad_bias.channel_params(5)
    s, b = info.channel_params(3)
    assert s.shape == b.shape == (3,)


def test_quantize_accepts_dict_metadata(tmp_path):
    import json

    p = str(tmp_path / "dm.ra")
    ra.write(p, np.ones((4, 2), np.float32), quantize="u8",
             metadata={"units": "mm"})
    meta = json.loads(ra.read_metadata(p))
    assert meta["units"] == "mm" and "ra_quant" in meta


def test_device_loader_stop_detaches_wedged_feeder(token_root):
    """A feeder wedged past the join timeout (blocked inside the wrapped
    loader) must not share the wrapped loader with a restarted pipeline:
    stop() swaps in an equivalent fresh DataLoader."""
    inner = DataLoader(RaDataset(token_root), 16, seed=6)
    gate = threading.Event()
    orig = DataLoader._produce.__get__(inner)

    def wedged(epoch, step, out=None):
        gate.wait()
        return orig(epoch, step, out)

    inner._produce = wedged
    dev = DeviceLoader(inner)
    dev._start()  # feeder blocks inside next(inner)
    time.sleep(0.1)
    feeder = dev._thread
    dev.stop()  # join times out (~2s): wrapped loader must be replaced
    assert dev.loader is not inner
    assert dev.loader.seed == 6 and dev.loader.batch_size == 16
    # the restarted pipeline iterates the reference sequence from scratch
    ref = DataLoader(RaDataset(token_root), 16, seed=6)
    b, r = next(dev), next(ref)
    assert np.array_equal(np.asarray(b["tokens"]), r["tokens"])
    assert b["_state"].__dict__ == r["_state"].__dict__
    gate.set()  # release the zombie; it must exit without stealing a batch
    feeder.join(timeout=5.0)
    assert not feeder.is_alive()
    b, r = next(dev), next(ref)
    assert np.array_equal(np.asarray(b["tokens"]), r["tokens"])
    dev.stop()
    ref.stop()
    inner.stop()
