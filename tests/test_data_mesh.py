"""Data-mesh invariants (DESIGN.md §15): shard ownership determinism and
minimal movement, exactly-once delivery under any host count, the
host-agnostic global shuffle, mid-epoch repartition (join AND leave)
preserving exactly-once, owned-shards-only I/O, elastic-state resume,
lockstep steps_per_epoch, stats aggregation, and global-array assembly."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro
from repro.core.spec import RawArrayError
from repro.data import DataLoader, LoaderState, RaDataset
from repro.data.dataset import DatasetBuilder
from repro.distributed.data_mesh import (
    DataMesh,
    EpochPlan,
    aggregate_stats,
    owners_table,
    shard_owners,
)

TOTAL, SHARD_ROWS, W = 320, 16, 2  # 20 shards; row i holds [i, i]


@pytest.fixture(scope="module")
def rid_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("mesh") / "ds")
    b = DatasetBuilder(root, {"rid": ((W,), np.int64)}, shard_rows=SHARD_ROWS)
    ids = np.arange(TOTAL, dtype=np.int64)
    b.append(rid=np.stack([ids] * W, axis=1))
    b.finish()
    return root


def _drain(dl, steps):
    out = [next(dl)["rid"][:, 0].copy() for _ in range(steps)]
    return np.concatenate(out) if out else np.empty(0, np.int64)


# ---- ownership ------------------------------------------------------------


def test_ownership_deterministic_and_minimal_movement():
    before = shard_owners(128, ["h0", "h1", "h2", "h3"], epoch=1)
    assert before == shard_owners(128, ["h0", "h1", "h2", "h3"], epoch=1)
    after = shard_owners(128, ["h0", "h1", "h2", "h3", "h4"], epoch=1)
    moved = [(x, y) for x, y in zip(before, after) if x != y]
    # consistent hashing: a new member only RECEIVES shards, and roughly 1/N
    assert 0 < len(moved) <= 64
    assert all(y == "h4" for _, y in moved)


def test_ownership_epoch_redeal(monkeypatch):
    hosts = ["a", "b", "c"]
    assert shard_owners(64, hosts, epoch=0) != shard_owners(64, hosts, epoch=1)
    monkeypatch.setenv("RA_MESH_EPOCH_REOWN", "0")
    assert shard_owners(64, hosts, epoch=0) == shard_owners(64, hosts, epoch=1)


# ---- plan invariants (pure, no dataset) -----------------------------------


@settings(max_examples=25, deadline=None)
@given(nhosts=st.integers(1, 6), seed=st.integers(0, 5), epoch=st.integers(0, 3))
def test_plan_streams_cover_exactly_once(nhosts, seed, epoch):
    shard_rows = [17, 3, 64, 1, 29, 16, 16, 40, 8, 11]
    hosts = [f"h{i}" for i in range(nhosts)]
    plan = EpochPlan(
        shard_rows, seed=seed, epoch=epoch, segments=[(0, hosts)], batch_size=4
    )
    allr = np.concatenate([plan.host_stream(h) for h in hosts])
    assert len(np.unique(allr)) == len(allr) == sum(shard_rows)


def test_global_shuffle_host_agnostic():
    shard_rows = [16] * 12
    hosts = ["a", "b", "c"]
    plans = [
        DataMesh(h, hosts).plan(shard_rows, seed=9, epoch=2, batch_size=4)
        for h in hosts
    ]
    assert len({p.steps() for p in plans}) == 1
    for h in hosts:
        ref = plans[0].host_order(h)
        for p in plans[1:]:
            assert np.array_equal(p.host_order(h), ref)


def test_shuffle_varies_by_epoch():
    mesh = DataMesh("a", ["a", "b"])
    p0 = mesh.plan([16] * 12, seed=1, epoch=0, batch_size=4)
    p1 = mesh.plan([16] * 12, seed=1, epoch=1, batch_size=4)
    assert not np.array_equal(
        p0.host_order("a")[: 4 * min(p0.steps(), p1.steps())],
        p1.host_order("a")[: 4 * min(p0.steps(), p1.steps())],
    )


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 5),
    n0=st.integers(1, 4),
    n1=st.integers(1, 5),
    t_frac=st.floats(0.0, 1.0),
)
def test_repartition_plan_preserves_exactly_once(seed, n0, n1, t_frac):
    shard_rows = [16] * 14
    B = 4
    start = [f"h{i}" for i in range(n0)]
    p0 = EpochPlan(shard_rows, seed=seed, epoch=0, segments=[(0, start)], batch_size=B)
    t = int(round(t_frac * p0.steps()))
    new = [f"h{i}" for i in range(n1)]
    plan = EpochPlan(
        shard_rows, seed=seed, epoch=0, segments=[(0, start), (t, new)], batch_size=B
    )
    union = sorted(set(start) | set(new))
    orders = [plan.host_order(h) for h in union]
    allr = np.concatenate([o[o >= 0] for o in orders])
    assert len(np.unique(allr)) == len(allr)  # no row delivered twice
    expected = t * B * len(start) + (plan.steps() - t) * B * len(new)
    assert len(allr) == expected  # no row dropped (vs the segment schedule)
    assert plan.dropped_rows() == sum(shard_rows) - len(allr)


# ---- loader end-to-end ----------------------------------------------------


def test_mesh_epoch_exactly_once_owned_only_byte_exact(rid_root):
    hosts, B = ["a", "b", "c"], 4
    loaders = {
        h: DataLoader(RaDataset(rid_root), B, seed=5, mesh=DataMesh(h, hosts))
        for h in hosts
    }
    spes = {h: dl.steps_per_epoch() for h, dl in loaders.items()}
    assert len(set(spes.values())) == 1  # lockstep across hosts
    n = spes["a"]
    seen = {h: _drain(dl, n) for h, dl in loaders.items()}
    for dl in loaders.values():
        dl.stop()
    allr = np.concatenate(list(seen.values()))
    assert len(np.unique(allr)) == len(allr)
    plan = loaders["a"]._mesh_plan(0)
    assert len(allr) + plan.dropped_rows() == TOTAL
    # a host only ever touches shards it owns (fd/fetch counter witness);
    # the prefetcher legitimately runs ahead into epoch 1's re-dealt deal
    for h, dl in loaders.items():
        owned = set(plan.owned_shards(h)) | set(dl._mesh_plan(1).owned_shards(h))
        assert set(dl.ds.shards_touched()) <= owned
    # byte-exact against a direct gather of the planned order
    ref = RaDataset(rid_root)
    for h in hosts:
        order = plan.host_order(h)[: n * B]
        assert np.array_equal(ref.gather(order)["rid"][:, 0], seen[h])


def test_loader_repartition_join_exactly_once(rid_root):
    start, B, T = ["a", "b"], 4, 3
    loaders = {
        h: DataLoader(RaDataset(rid_root), B, seed=13, mesh=DataMesh(h, start))
        for h in start
    }
    seen = {h: [_drain(loaders[h], 1) for _ in range(T)] for h in start}
    new = ["a", "b", "c"]
    for h in start:
        st_ = loaders[h].repartition(new)
        assert (st_.epoch, st_.step) == (0, T)
    # the joining host rebuilds the schedule from the segment history alone
    segs = loaders["a"].mesh.segments_for(0)
    mesh_c = DataMesh("c", new)
    mesh_c.load_segments(0, segs)
    dl_c = DataLoader(RaDataset(rid_root), B, seed=13, mesh=mesh_c)
    dl_c.seek(0, T)
    loaders["c"] = dl_c
    seen["c"] = []
    spe = loaders["a"].steps_per_epoch()
    assert spe > T
    for h, dl in loaders.items():
        while len(seen[h]) < spe - (T if h == "c" else 0):
            seen[h].append(_drain(dl, 1))
        dl.stop()
    allr = np.concatenate([np.concatenate(v) for v in seen.values()])
    assert len(np.unique(allr)) == len(allr)
    assert len(allr) == T * B * 2 + (spe - T) * B * 3


def test_loader_repartition_leave_exactly_once(rid_root):
    hosts, B, T = ["a", "b", "c"], 4, 2
    loaders = {
        h: DataLoader(RaDataset(rid_root), B, seed=11, mesh=DataMesh(h, hosts))
        for h in hosts
    }
    seen = {h: [_drain(loaders[h], 1) for _ in range(T)] for h in hosts}
    survivors = ["a", "b"]
    for h in survivors:
        assert loaders[h].repartition(survivors).step == T
    loaders["c"].stop()
    spe = loaders["a"].steps_per_epoch()
    for h in survivors:
        while len(seen[h]) < spe:
            seen[h].append(_drain(loaders[h], 1))
        loaders[h].stop()
    allr = np.concatenate([np.concatenate(v) for v in seen.values()])
    assert len(np.unique(allr)) == len(allr)
    assert len(allr) == T * B * 3 + (spe - T) * B * 2


def test_mesh_state_resume_after_repartition(rid_root):
    B = 4
    hosts = ["a", "b"]
    loaders = {
        h: DataLoader(RaDataset(rid_root), B, seed=21, mesh=DataMesh(h, hosts))
        for h in hosts
    }
    for h in hosts:
        _drain(loaders[h], 2)
    for h in hosts:
        loaders[h].repartition(["a"])
    loaders["b"].stop()
    bt = next(loaders["a"])
    st_ = bt["_state"]
    assert st_.mesh_segments == [(0, ("a", "b")), (2, ("a",))]
    # serialization round-trip (what rides in a checkpoint)
    rt = LoaderState.from_dict(st_.to_dict())
    assert rt.__dict__ == st_.__dict__
    follow = next(loaders["a"])
    loaders["a"].stop()
    # a fresh loader + mesh restored from the state reproduces the follower
    dl2 = DataLoader(RaDataset(rid_root), B, seed=21, mesh=DataMesh("a", ["a", "b"]))
    dl2.restore(rt)
    nxt = next(dl2)
    dl2.stop()
    assert nxt["_state"].__dict__ == follow["_state"].__dict__
    assert np.array_equal(nxt["rid"], follow["rid"])


def test_single_host_defaults_byte_identical(rid_root):
    """mesh=None keeps the seed-era contract bit for bit: the epoch order is
    ``default_rng((seed, epoch)).permutation(host_rows)`` sliced per step."""
    ds = RaDataset(rid_root)
    dl = DataLoader(ds, 16, seed=4)
    got = [next(dl) for _ in range(4)]
    dl.stop()
    order = np.random.default_rng((4, 0)).permutation(np.arange(TOTAL))
    for t, bt in enumerate(got):
        assert np.array_equal(bt["rid"][:, 0], order[t * 16 : (t + 1) * 16])
        assert (bt["_state"].epoch, bt["_state"].step) == (0, t)


def test_steps_per_epoch_uniform_nonmesh(rid_root):
    ds = RaDataset(rid_root)
    for hosts in (2, 3, 7):
        spes = {
            DataLoader(ds, 8, host_id=h, host_count=hosts).steps_per_epoch()
            for h in range(hosts)
        }
        assert len(spes) == 1  # remainder host no longer diverges
        spe = spes.pop()
        dl = DataLoader(ds, 8, host_id=hosts - 1, host_count=hosts)
        assert dl.stats()["dropped_tail_rows"] == TOTAL - spe * 8 * hosts


def test_zero_steps_is_sticky_error(rid_root):
    dl = DataLoader(RaDataset(rid_root), TOTAL + 1, mesh=DataMesh("a", ["a"]))
    with pytest.raises(RawArrayError, match="zero steps"):
        next(dl)
    with pytest.raises(RawArrayError):  # sticky, not a hang
        next(dl)
    dl.stop()


# ---- observability --------------------------------------------------------


def test_owners_table_and_racat(rid_root, capsys):
    table = owners_table(rid_root, ["a", "b", "c"])
    assert len(table["shards"]) == TOTAL // SHARD_ROWS
    assert table["total_rows"] == TOTAL
    assert table["total_bytes"] == TOTAL * W * 8
    assert sum(t["bytes"] for t in table["per_host"].values()) == table["total_bytes"]
    assert table["imbalance"] >= 1.0
    # the CLI: zero payload reads, table + totals + imbalance
    from repro.core import racat

    assert racat.main(["owners", rid_root, "--hosts", "3"]) == 0
    out = capsys.readouterr().out
    assert "imbalance" in out and "host0" in out and "shard" in out


def test_aggregate_stats_straggler():
    stats = [
        {"host_id": 0.0, "loader_produce_s": 1.0, "loader_wait_s": 0.1,
         "batches": 10.0, "dropped_tail_rows": 5.0},
        {"host_id": 1.0, "loader_produce_s": 3.0, "loader_wait_s": 0.2,
         "batches": 10.0, "dropped_tail_rows": 5.0},
    ]
    agg = aggregate_stats(stats)
    assert agg["hosts"] == 2.0
    assert agg["batches"] == 20.0
    assert agg["loader_produce_s"] == 4.0
    assert agg["loader_produce_s_max"] == 3.0
    assert agg["straggler_host"] == 1.0
    assert agg["produce_skew"] == 1.5
    assert agg["dropped_tail_rows"] == 5.0  # global: agreed across hosts


def test_loader_stats_are_aggregatable(rid_root):
    hosts = ["a", "b"]
    per = []
    for h in hosts:
        dl = DataLoader(RaDataset(rid_root), 8, seed=2, mesh=DataMesh(h, hosts))
        _drain(dl, 2)
        dl.stop()
        per.append(dl.stats())
    agg = aggregate_stats(per)
    assert agg["hosts"] == 2.0 and agg["batches"] == 4.0
    assert "straggler_host" in agg and "dropped_tail_rows" in agg


# ---- device / global assembly ---------------------------------------------


def test_device_loader_global_single_host(rid_root):
    jax = pytest.importorskip("jax")
    from repro.data import DeviceLoader

    mesh = DataMesh("solo", ["solo"])
    dev = DeviceLoader(DataLoader(RaDataset(rid_root), 8, seed=2, mesh=mesh))
    assert dev.global_arrays
    bt = next(dev)
    assert isinstance(bt["rid"], jax.Array) and bt["rid"].shape == (8, W)
    ref = DataLoader(RaDataset(rid_root), 8, seed=2, mesh=DataMesh("solo", ["solo"]))
    want = next(ref)["rid"]
    ref.stop()
    assert np.array_equal(np.asarray(bt["rid"]), want)
    dev.stop()


_SIM = r"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 " + os.environ.get("XLA_FLAGS", "")
)
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from repro.data import DataLoader, RaDataset
from repro.data.dataset import DatasetBuilder
from repro.distributed.data_mesh import DataMesh

# 64 shards so every one of 4 hosts owns a workable share under the ring
root = os.path.join(os.environ["DS_ROOT"], "sim_ds")
b = DatasetBuilder(root, {"rid": ((2,), np.int64)}, shard_rows=8)
ids = np.arange(512, dtype=np.int64)
b.append(rid=np.stack([ids, ids], axis=1))
b.finish()
hosts = ["h0", "h1", "h2", "h3"]
B = 4
devs = jax.devices()
assert len(devs) == 4, devs
sharding = NamedSharding(Mesh(np.array(devs), ("data",)), PartitionSpec("data"))
loaders = [DataLoader(RaDataset(root), B, seed=3, mesh=DataMesh(h, hosts)) for h in hosts]
spe = loaders[0].steps_per_epoch()
assert spe > 0

@jax.jit
def step(x):  # a collective-shaped reduction over the global batch
    return jnp.sum(x)

for t in range(min(spe, 3)):
    batches = [next(dl) for dl in loaders]
    shards = [jax.device_put(b["rid"], d) for b, d in zip(batches, devs)]
    g = jax.make_array_from_single_device_arrays((B * 4, 2), sharding, shards)
    assert g.shape == (B * 4, 2)
    want = sum(int(b["rid"].sum()) for b in batches)
    assert int(step(g)) == want
for dl in loaders:
    dl.stop()
print("SIM_OK")
"""


def test_global_assembly_simulated_four_hosts(rid_root):
    """Four simulated mesh hosts in one process (forced host-platform device
    count): per-host mesh loaders feed device shards that assemble into one
    global jax.Array consumed by a jitted step."""
    pytest.importorskip("jax")
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ)
    env["DS_ROOT"] = os.path.dirname(rid_root)  # sim builds its own dataset here
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [src, env.get("PYTHONPATH", "")])
    )
    out = subprocess.run(
        [sys.executable, "-c", _SIM],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0 and "SIM_OK" in out.stdout, (out.stdout, out.stderr)


# ---- the data-parallel image feed against its plain reference ---------------

_FEED = r"""
import importlib.util, json, os, sys
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 " + os.environ.get("XLA_FLAGS", "")
)
import numpy as np, jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from repro.core import QuantInfo
from repro.data import DataLoader, DeviceLoader, RaDataset
from repro.data.dataset import DatasetBuilder
from repro.distributed import data_mesh
from repro.distributed.data_mesh import DataMesh

repo, root = sys.argv[1], sys.argv[2]
spec = importlib.util.spec_from_file_location(
    "dp4_reference", os.path.join(repo, "bench", "configs", "imagenet_u8_dp4.py"))
ref = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ref)
with open(os.path.join(repo, "bench", "configs", "imagenet_u8_dp4.json")) as f:
    cfg = json.load(f)
# 64 rows of 8x8x3 u8 codes in shards of 16; a global batch of 16, 4 a device
cfg.update({"image": [8, 8, 3], "images": 64, "shard_rows": 16, "classes": 10})
SEED, B = 2147483907, 16
scale, bias = ref.scale_bias(cfg)
b = DatasetBuilder(root, {"image": ((8, 8, 3), "float32"), "label": ((), "int32")},
                   shard_rows=16, quantize={"image": QuantInfo(scale=scale, bias=bias,
                                                               orig_dtype="float32")})
for block in range(4):
    rows = np.arange(16 * block, 16 * (block + 1))
    b.append(image=ref.dequant(ref.block_codes(cfg, SEED, block), cfg), label=ref.labels(cfg, rows))
b.finish()
devices = jax.devices()
assert len(devices) == 4, devices


def read(batch):
    labels = {s.device: s for s in batch["label"].addressable_shards}
    return [(devices.index(s.device), s.index[0].start or 0,
             labels[s.device].index[0].start or 0, np.asarray(s.data),
             np.asarray(labels[s.device].data)) for s in batch["image"].addressable_shards]


def checks():
    # two epochs of global batches, each compared shard by shard; the
    # second epoch's labels counted against the set's
    dl = DeviceLoader(DataLoader(RaDataset(root), B, seed=SEED, mesh=DataMesh("host0", ["host0"])),
                      interpret=True)
    spe = dl.steps_per_epoch()
    rows = ref.Rows(cfg, SEED)
    worst = {"image_err": 0.0, "rows_repeated": 0.0, "shard_misplaced": 0.0}
    epoch = []
    for _ in range(2 * spe):
        batch = next(dl)
        assert batch["image"].shape == (B, 8, 8, 3) and batch["label"].shape == (B,)
        r = ref.compare_batch(read(batch), rows, cfg, len(devices), B)
        worst = {k: max(v, r[k]) for k, v in worst.items()}
        if batch["_state"].epoch == 1:
            epoch.append(np.asarray(batch["label"]))
    dl.stop()
    return {**worst, "epoch_rows_wrong": ref.epoch_rows_wrong(epoch, cfg)}


out = {"sound": checks()}
sharding, nxt = data_mesh.data_sharding, DataLoader.__next__


def swapped(axis_name="data"):
    # devices 0 and 1 exchange places in the mesh the global arrays name
    d = jax.devices()
    return NamedSharding(Mesh(np.array([d[1], d[0]] + d[2:]), (axis_name,)),
                         PartitionSpec(axis_name))


def repeated(self):
    b = nxt(self)
    for k in ("image", "label"):
        b[k] = b[k].copy()
        b[k][B // 4] = b[k][0]  # device 1's first row is device 0's first
    return b


first = {}


def dropped(self):
    b = nxt(self)
    st = b["_state"]
    if st.step == 0:
        first[st.epoch] = {k: b[k].copy() for k in ("image", "label")}
    elif st.step == 1:
        # step 1's first row becomes a row of step 0 under another label
        f = first[st.epoch]
        j = int(np.flatnonzero(f["label"] != b["label"][0])[0])
        for k in ("image", "label"):
            b[k] = b[k].copy()
            b[k][0] = f[k][j]
    return b


data_mesh.data_sharding = swapped
out["two shards swapped"] = checks()
data_mesh.data_sharding = sharding
for name, fault in (("one row repeated across shards", repeated),
                    ("one row dropped from an epoch", dropped)):
    DataLoader.__next__ = fault
    out[name] = checks()
DataLoader.__next__ = nxt
print("FEED " + json.dumps(out))
"""

# the cell's limits: image_err in quantization steps (0.25 on the chip; the
# CPU's float32 reads about 1e-5), every count 0
_FEED_LIMITS = {"image_err": 0.01, "rows_repeated": 0, "shard_misplaced": 0, "epoch_rows_wrong": 0}


@pytest.fixture(scope="module")
def feed_checks(tmp_path_factory):
    pytest.importorskip("jax")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [src, env.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-c", _FEED, repo, str(tmp_path_factory.mktemp("feed") / "images")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("FEED ")]
    assert out.returncode == 0 and lines, (out.stdout, out.stderr[-4000:])
    return json.loads(lines[-1][5:])


def test_global_image_feed_passes_the_data_parallel_reference(feed_checks):
    """A one-host DataMesh over four devices: DeviceLoader's global arrays
    hold distinct rows, each on the device of its global position, each
    decoding to the image its label names, and an epoch delivers every row
    once (``bench/configs/imagenet_u8_dp4.py``)."""
    got = feed_checks["sound"]
    assert all(got[k] <= lim for k, lim in _FEED_LIMITS.items()), got


@pytest.mark.parametrize("fault,check", [("two shards swapped", "shard_misplaced"),
                                         ("one row repeated across shards", "rows_repeated"),
                                         ("one row dropped from an epoch", "epoch_rows_wrong")])
def test_global_image_feed_fault_fails_the_reference(feed_checks, fault, check):
    got = feed_checks[fault]
    assert got[check] > _FEED_LIMITS[check], got


# ---- a packed image field through the global assembly ------------------------

_PACKED = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 " + os.environ.get("XLA_FLAGS", "")
)
import numpy as np, jax
from repro.core import QuantInfo, obs
from repro.data import DataLoader, DeviceLoader, RaDataset
from repro.data.dataset import DatasetBuilder
from repro.distributed import data_mesh
from repro.distributed.data_mesh import DataMesh

root = sys.argv[1]
# scales of few significant bits: q*scale is exact in float32, so the host's
# multiply-then-add and a fused multiply-add agree bit for bit
scale = np.array([3, 5, 7], np.float32) / 1024
bias = np.array([-2.1, -1.9, 0.4], np.float32)
codes = np.random.default_rng(8).integers(0, 256, (64, 8, 8, 3)).astype(np.float32)
b = DatasetBuilder(root, {"image": ((8, 8, 3), "float32"), "label": ((), "int32")},
                   shard_rows=16, quantize={"image": QuantInfo(scale=scale, bias=bias,
                                                               orig_dtype="float32")})
b.append(image=codes * scale + bias, label=np.arange(64, dtype=np.int32))
b.finish()
devices = jax.devices()
assert len(devices) == 4, devices
B, per = 16, 4  # a device's block: 4 x 8x8x3 = 768 codes = 2 rows of 384 lanes
packed = obs.counter("device_loader.packed")
host = DataLoader(RaDataset(root), B, seed=9, mesh=DataMesh("host0", ["host0"]))
dl = DeviceLoader(DataLoader(RaDataset(root), B, seed=9, mesh=DataMesh("host0", ["host0"])),
                  interpret=True)
before = packed.value
shards = 0
for _ in range(6):
    want, got = next(host), next(dl)
    for k in ("image", "label"):
        g = got[k]
        assert g.shape == want[k].shape and g.dtype == want[k].dtype, (k, g.shape, g.dtype)
        assert g.sharding == data_mesh.data_sharding(), g.sharding
        for s in g.addressable_shards:
            i = devices.index(s.device)
            assert np.arange(B)[s.index[0]].tolist() == list(range(per * i, per * (i + 1)))
            assert np.array_equal(np.asarray(s.data), want[k][s.index]), (k, i)
            shards += 1
dl.stop()
host.stop()
print("PACKED " + json.dumps({"packed": packed.value - before, "shards": shards,
                              "puts": dl.stats()["h2d_batches"]}))
"""


def test_global_assembly_of_a_packed_image_field(tmp_path):
    """On four devices an 8x8x3 u8 image field crosses as each device's
    packed ``(2, 384)`` block, is decoded there and reshaped back: the
    global arrays equal the host loader's dequantized batch shard by shard
    (``addressable_shards`` index and values) with the same shape, dtype
    and sharding, and ``device_loader.packed`` counts one block per device
    per batch."""
    pytest.importorskip("jax")
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [src, env.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-c", _PACKED, str(tmp_path / "images")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("PACKED ")]
    assert out.returncode == 0 and lines, (out.stdout, out.stderr[-4000:])
    got = json.loads(lines[-1][7:])
    assert got["shards"] == 6 * 2 * 4
    assert got["puts"] >= 6 and got["packed"] == 4 * got["puts"], got
