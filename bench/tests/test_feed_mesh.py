"""Tests of the data-parallel feed cell (``feed.imagenet.u8.dp4``) on the
CPU: a tiny run through the harness on four host-platform devices is
``correct``, planted faults in the program each turn it false, and the
cell's six per-layer readers read fabricated program spans and a
fabricated four-device trace.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

import jax  # noqa: E402

from bench import harness, peaks, readers, trace  # noqa: E402
from bench.tests import tiny  # noqa: E402
from repro.core import obs  # noqa: E402

# CPU programs stay out of the checkout's compile cache
jax.config.update("jax_enable_compilation_cache", False)

CELL, TINY_CELL = "feed.imagenet.u8.dp4", "feed.dp4.tiny"
TINY_IMAGES = {"image": [8, 8, 3], "images": 64, "shard_rows": 16, "classes": 10}
TINY_LIMITS = {"image_err": 0.01, "rows_repeated": 0, "shard_misplaced": 0, "epoch_rows_wrong": 0}


def make_root(tmp: str) -> str:
    """``tiny.make_root``'s checkout with the data-parallel cell added at a
    tiny size: 64 images of 8x8x3, a global batch of 16 over four devices."""
    root = tiny.make_root(tmp)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = next(w for w in json.load(f)["workloads"] if w["name"] == CELL)
    path = os.path.join(root, "bench", "configs", real["config"] + ".json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(TINY_IMAGES)
    with open(path, "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "bench", "traffic", real["traffic"] + ".json")
    with open(path) as f:
        tr = json.load(f)
    tr.update({"batch": 16, "warmup_batches": 1})
    with open(path, "w") as f:
        json.dump(tr, f)
    with open(os.path.join(root, "bench", "cells", TINY_CELL + ".json"), "w") as f:
        json.dump({"limits": TINY_LIMITS}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({**real, "name": TINY_CELL})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(TINY_CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


# a tiny run in a process of its own, on four host-platform devices, with
# one fault planted in the program (or none)
_RUN = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4 " + os.environ.get("XLA_FLAGS", "")
root, fault = sys.argv[1], sys.argv[2]
sys.path[:0] = [root, os.path.join(root, "src")]
import jax
import numpy as np
jax.config.update("jax_enable_compilation_cache", False)
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from repro.data.loader import DataLoader
from repro.distributed import data_mesh

nxt = DataLoader.__next__
if fault == "shards swapped":
    # chips 0 and 1 exchange their places in the mesh the global arrays are declared on
    def swapped(axis_name="data"):
        d = jax.devices()
        return NamedSharding(Mesh(np.array([d[1], d[0]] + d[2:]), (axis_name,)), PartitionSpec(axis_name))
    data_mesh.data_sharding = swapped
elif fault == "row repeated across shards":
    def repeated(self):
        b = nxt(self)
        per = len(b["label"]) // len(jax.devices())
        for k in ("image", "label"):
            b[k] = b[k].copy()
            b[k][per] = b[k][0]  # chip 1's first row is chip 0's first
        return b
    DataLoader.__next__ = repeated
elif fault == "row dropped from an epoch":
    first = {}
    def dropped(self):
        b = nxt(self)
        st = b["_state"]
        if st.step == 0:
            first[st.epoch] = {k: b[k].copy() for k in ("image", "label")}
        elif st.step == 1 and st.epoch in first:
            # step 1's first row is replaced by a row of step 0 of another label
            f = first[st.epoch]
            j = int(np.flatnonzero(f["label"] != b["label"][0])[0])
            for k in ("image", "label"):
                b[k] = b[k].copy()
                b[k][0] = f[k][j]
        return b
    DataLoader.__next__ = dropped
from bench import harness
line = harness.execute(["--workload", "feed.dp4.tiny", "--seed", "2147483901", "--seconds", "0.5"],
                       require_tpu=False, root=root)
print(json.dumps(line))
"""

FAULTS = {"shards swapped": "shard_misplaced", "row repeated across shards": "rows_repeated",
          "row dropped from an epoch": "epoch_rows_wrong"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("feed_mesh")))


def _run(root, fault):
    p = subprocess.run([sys.executable, "-c", _RUN, root, fault], capture_output=True, text=True,
                       timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_sound_tiny_run_is_correct(root):
    line = _run(root, "none")
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4 and line["device"]["platform"] == "cpu"
    assert line["metrics"]["feed_images_per_s"]["value"] > 0
    assert set(line["checks"]) == set(TINY_LIMITS)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_mesh_feed_is_not_correct(root, fault):
    line = _run(root, fault)
    assert not line["correct"], line["checks"]
    check = line["checks"][FAULTS[fault]]
    assert check["value"] > check["limit"], line["checks"]


# ------------------------------------------------------------------ readers
def _reader(name):
    return harness.load_module(harness.resolve(CELL, REPO).metric_file(name)).read


def _run_at(lo, hi):
    t0 = lo - 1000
    return harness.Run(cell=harness.resolve(CELL, REPO), seed=1, seconds=1.0, trace=True,
                       t_process=0.0, workdir="", spans=[(trace.WINDOW, lo - t0, hi - t0)],
                       _trace_t0=t0)


def test_span_readers_read_per_global_batch():
    lo = obs.now() + 10**15
    hi = lo + 100_000
    # two global batches in the window: each a loader.wait, a produce, an
    # H2D holding four shard puts of 1,000 ns each
    for k in range(2):
        a = lo + 10_000 + 40_000 * k
        obs.record("loader.wait", a, a + 100)
        obs.record("loader.produce", a - 3_000, a - 1_000)
        obs.record("device_loader.h2d", a + 200, a + 5_200)
        for d in range(4):
            obs.record("device_loader.shard_put", a + 300 + 1_000 * d, a + 1_300 + 1_000 * d)
    obs.record("device_loader.shard_put", hi + 10, hi + 20)  # after the window
    run = _run_at(lo, hi)
    assert _reader("loader_produce_ms.feed_dp4")(run, None, {}) == pytest.approx(2_000e-9 * 1e3)
    assert _reader("h2d_ms.feed_dp4")(run, None, {}) == pytest.approx(5_000e-9 * 1e3)
    assert _reader("shard_put_ms.feed_dp4")(run, None, {}) == pytest.approx(4_000e-9 * 1e3)


def test_shard_puts_read_nothing_from_a_program_without_them():
    lo = obs.now() + 10**15
    hi = lo + 100_000
    obs.record("loader.wait", lo + 10, lo + 20)
    obs.record("device_loader.h2d", lo + 30, lo + 90)
    run = _run_at(lo, hi)
    assert _reader("h2d_ms.feed_dp4")(run, None, {}) == pytest.approx(60e-9 * 1e3)
    assert _reader("shard_put_ms.feed_dp4")(run, None, {}) is None


@pytest.mark.parametrize("name", ["loader_produce_ms.feed_dp4", "h2d_ms.feed_dp4",
                                  "shard_put_ms.feed_dp4"])
def test_span_readers_read_nothing_without_a_window(name):
    run = harness.Run(cell=harness.resolve(CELL, REPO), seed=1, seconds=1.0, trace=True,
                      t_process=0.0, workdir="")
    assert _reader(name)(run, None, {}) is None


def test_trace_readers_take_all_four_devices(monkeypatch):
    monkeypatch.setattr(readers, "peak", lambda: peaks.peak("TPU v5 lite"))
    hlo = ("%dequant_u8.1 = f32[100352,384]{1,0:T(8,128)} custom-call(u8[100352,384]{1,0} %r, "
           "f32[1,384]{1,0} %s, f32[1,384]{1,0} %b), custom_call_target=\"tpu_custom_call\"")
    n = 100352 * 384
    least = (n * 5 + 2 * 4 * 384) / 819e9
    # device k runs the kernel for (1 + k) times its least time, and is busy
    # (1 + k) ms of a 10 ms window
    devices = {}
    for k in range(4):
        spent = int(least * (1 + k) * 1e9)
        devices[f"/device:TPU:{k}"] = [trace.Event(hlo, 0, spent),
                                       trace.Event("%copy.2 = u8[256,224,224,3] copy()", 5_000_000,
                                                   5_000_000 + (1 + k) * 1_000_000 - spent)]
    with open(os.path.join(REPO, "bench", "traffic", "feed.u8.dp4.json")) as f:
        kernels = {k: v["match"] for k, v in json.load(f)["kernels"].items()}
    summary = trace.summarize(trace.Trace(devices, [trace.Event(trace.WINDOW, 0, 10_000_000)]),
                              kernels=kernels)
    run = harness.Run(cell=harness.resolve(CELL, REPO), seed=1, seconds=1.0, trace=True,
                      t_process=0.0, workdir="")
    assert len(summary.kernel_calls["dequant"]) == 4
    # busy 1, 2, 3 and 4 ms of 10: 2.5 ms on the mean
    assert _reader("idle_share.feed_dp4")(run, summary, {}) == pytest.approx(75.0, rel=1e-3)
    share = _reader("dequant_roofline.feed_dp4")(run, summary, {})
    assert share == pytest.approx(100 * 4 / (1 + 2 + 3 + 4), rel=1e-3)
    assert 0 < share <= 100
    mfu = _reader("mfu.feed_dp4")(run, summary, {})
    assert mfu == pytest.approx(100 * 4 * 2 * n / (10e-3 * 4 * 197e12), rel=1e-6)
    for name in ("idle_share.feed_dp4", "dequant_roofline.feed_dp4", "mfu.feed_dp4"):
        assert _reader(name)(run, None, {}) is None
