"""Tests of the benchmark itself, on the CPU: the trace reduction, the
operation and byte counts, the peaks table, cells found by name, the
refusal to run without a chip, and ``correct`` turning false when the
timed path is broken underneath a run.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

import jax  # noqa: E402

from bench import costs, harness, peaks, trace  # noqa: E402
from bench.tests import tiny  # noqa: E402

# CPU programs stay out of the checkout's compile cache
jax.config.update("jax_enable_compilation_cache", False)


# ------------------------------------------------------------ reduction
def _ev(name, start, end):
    return trace.Event(name, start, end)


def test_trace_reduces_to_busy_idle_and_kernel_time():
    t = trace.Trace(
        devices={"/device:TPU:0": [_ev("%fusion.1 = f32[8] fusion(...)", 100, 300),
                                   _ev("%dequant_u8.3 = f32[8,384] custom-call(...)", 250, 400),
                                   _ev("%copy.2 = u8[8,384] copy(...), op_name=dequant_u8", 600, 700),
                                   _ev("%fusion.0 = f32[8] fusion(...)", 0, 50)]},
        host=[_ev("bench.window", 100, 1100), _ev("feed.next", 380, 650),
              _ev("feed.ready", 700, 1100)],
    )
    with open(os.path.join(REPO, "bench", "traffic", "feed.u8.json")) as f:
        kernels = {k: v["match"] for k, v in json.load(f)["kernels"].items()}
    s = trace.summarize(t, kernels=kernels)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(400e-9)  # [100,400) and [600,700)
    assert s.idle_share == pytest.approx(0.6)
    assert s.kernel_calls == {"dequant": [("%dequant_u8.3 = f32[8,384] custom-call(...)", pytest.approx(150e-9))]}
    assert s.op_s["%fusion.1 = f32[8] fusion(...)"] == pytest.approx(200e-9)
    assert not any(n.startswith("%fusion.0") for n in s.op_s)  # before the window
    gaps = dict(s.breakdown()["idle_gaps"])
    assert gaps == {"feed.next": pytest.approx(200e-9), "feed.ready": pytest.approx(400e-9)}


def test_union_merges_overlaps():
    assert trace.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]


# ----------------------------------------------------- operations, bytes
def test_dequant_bytes_for_an_image_batch():
    # the kernel's call on one (256, 224, 224, 3) batch, as the trace names it
    # (the 3-wide last axis repacked to 384 lanes)
    hlo = ("%dequant_u8.1 = f32[100352,384]{1,0:T(8,128)} custom-call(u8[100352,384]"
           "{1,0:T(8,128)(4,1)S(1)} %reshape.2, f32[1,384]{1,0:T(1,128)S(1)} %copy-done, "
           "f32[1,384]{1,0:T(1,128)S(1)} %copy-done.1), custom_call_target=\"tpu_custom_call\", "
           "operand_layout_constraints={u8[100352,384]{1,0}, f32[1,384]{1,0}, f32[1,384]{1,0}}")
    ops, nbytes = costs.elementwise_cost(hlo, 2)
    n = 256 * 224 * 224 * 3
    assert ops == 2 * n
    assert nbytes == n * 1 + n * 4 + 2 * 4 * 384
    t, bound = costs.roofline_time(ops, nbytes, peaks.peak("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")


# ---------------------------------------------------------- found by name
def test_every_cell_resolves_to_its_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = harness.resolve(w["name"], REPO)
        for path in cell.files():
            assert os.path.isfile(path), path
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e and m["moves"] in reported, (w["name"], m["name"])
        assert set(cell.limits), w["name"]


def test_a_new_configuration_is_found_by_name(tmp_path):
    root = tiny.make_root(str(tmp_path))
    cfgs = os.path.join(root, "bench", "configs")
    with open(os.path.join(cfgs, "olmo1b.json")) as f:
        cfg = json.load(f)
    cfg["model"]["n_layers"] = 1
    with open(os.path.join(cfgs, "olmo1b_one_layer.json"), "w") as f:
        json.dump(cfg, f)
    os.symlink(os.path.join(cfgs, "olmo1b.py"), os.path.join(cfgs, "olmo1b_one_layer.py"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "olmo1b_one_layer", "source": "test",
                             "file": "bench/configs/olmo1b_one_layer.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "coldstart.one_layer", "config": "olmo1b_one_layer",
                               "traffic": "coldstart.url_q8", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "coldstart.tiny" in m.get("workloads", []):
            m["workloads"].append("coldstart.one_layer")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with open(os.path.join(root, "bench", "cells", "coldstart.one_layer.json"), "w") as f:
        json.dump({"limits": tiny.LIMITS["coldstart.tiny"]}, f)
    cell = harness.resolve("coldstart.one_layer", root)
    assert cell.config["model"]["n_layers"] == 1
    assert cell.reference_file.endswith("olmo1b_one_layer.py")
    line = harness.execute(["--workload", "coldstart.one_layer", "--seed", "5", "--seconds", "0.5"],
                           require_tpu=False, root=root)
    assert line["correct"], line["checks"]


def test_the_command_exits_nonzero_without_a_chip():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "feed.imagenet.u8",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout


# ------------------------------------------------------ correct, broken
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _run(root, cell, seed=7, seconds=0.5):
    return harness.execute(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds)],
                           require_tpu=False, root=root)


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_a_sound_run_is_correct(root, cell):
    line = _run(root, cell)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"


def _half_batch_rows(monkeypatch):
    from repro.data.loader import DataLoader

    nxt = DataLoader.__next__

    def repeated(self):
        b = nxt(self)
        out = dict(b)
        for k, a in b.items():
            if k != "_state":
                a = a.copy()
                a[len(a) // 2:] = a[: len(a) - len(a) // 2]
                out[k] = a
        return out

    monkeypatch.setattr(DataLoader, "__next__", repeated)


def _altered_dequant(monkeypatch):
    from repro.kernels import ops

    dq = ops.dequant_u8

    def altered(x, *a, **kw):
        out = dq(x, *a, **kw)
        return out.at[(0,) * out.ndim].add(1)

    altered.lower = dq.lower  # the restore's warmup compiles ahead
    monkeypatch.setattr(ops, "dequant_u8", altered)


FAULTS = {
    ("coldstart.tiny", "weight altered"): _altered_dequant,
    ("feed.tiny", "pixel altered"): _altered_dequant,
    ("feed.tiny", "half the batch"): _half_batch_rows,
}


@pytest.mark.parametrize("cell,fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, cell, fault):
    FAULTS[cell, fault](monkeypatch)
    line = _run(root, cell)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_the_control_is_not_correct(root, cell, capsys):
    from bench import control

    assert control.main(["--workload", cell, "--seeds", "3,4,5"], require_tpu=False, root=root) == 0
    smallest = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["smallest"]
    limits = tiny.LIMITS[cell]
    assert any(smallest[k] > limits[k] for k in smallest), (smallest, limits)


def test_every_per_layer_reader_reads_a_synthetic_trace(monkeypatch):
    from bench import readers

    monkeypatch.setattr(readers, "peak", lambda: peaks.peak("TPU v5 lite"))
    hlo = ("%dequant_u8.1 = bf16[2048,2048]{1,0} custom-call(u8[2048,2048]{1,0} %x, "
           "f32[1,2048]{1,0} %s, f32[1,2048]{1,0} %b), custom_call_target=\"tpu_custom_call\"")
    summary = trace.Summary(window_s=2.0, busy_s=0.5, op_s={hlo: 0.1},
                            kernel_calls={"dequant": [(hlo, 1e-4)] * 3}, gaps=[])
    counters = {"restore_h2d_s_total": 0.3, "cold_starts": 3, "loader_produce_s_window": 1.0,
                "batches_window": 20, "h2d_s_window": 0.8, "h2d_batches_window": 20}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.resolve(w["name"], REPO)
        run = harness.Run(cell=cell, seed=1, seconds=2.0, trace=True, t_process=0.0, workdir="",
                          counters=dict(counters), window_s=2.0)
        for m in cell.per_layer:
            value = harness.load_module(cell.metric_file(m["name"])).read(run=run, summary=summary, e2e={})
            assert value is not None and value > 0, m["name"]
            if m["unit"] == "%":
                assert value <= 100, (m["name"], value)
    least = (2048 * 2048 * 3 + 2 * 4 * 2048) / 819e9  # u8 in, bf16 out, scale and bias in
    assert harness.load_module(cell.metric_file("dequant_roofline.feed")).read(
        run=run, summary=summary, e2e={}) == pytest.approx(100 * least / 1e-4, rel=1e-3)
