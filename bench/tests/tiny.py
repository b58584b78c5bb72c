"""A copy of the benchmark at sizes a CPU test can hold, for driving the
harness end to end without a chip."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

TINY_MODEL = {"d_model": 64, "n_heads": 4, "n_kv_heads": 4, "d_ff": 128, "vocab": 256,
              "n_layers": 2, "max_seq": 64}
TINY_IMAGES = {"image": [8, 8, 3], "images": 96, "shard_rows": 32, "classes": 10}
# limits for the tiny cells, from sound CPU runs at these sizes
LIMITS = {
    "coldstart.tiny": {"leaf_err": 0.01},
    "feed.tiny": {"image_err": 0.01, "rows_repeated": 0},
}
CELLS = {
    "coldstart.tiny": ("olmo1b", "coldstart.url_q8", {}),
    "feed.tiny": ("imagenet_u8", "feed.u8", {"batch": 16, "warmup_batches": 1}),
}


def make_root(tmp: str) -> str:
    """A checkout under ``tmp`` holding the benchmark's files with every
    configuration and traffic mix cut to a tiny size; cells as in ``CELLS``."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfgs = os.path.join(root, "bench", "configs")
    for name, patch in (("olmo1b", {"model": TINY_MODEL}), ("imagenet_u8", TINY_IMAGES)):
        with open(os.path.join(cfgs, name + ".json")) as f:
            cfg = json.load(f)
        if "model" in patch:
            cfg["model"].update(patch["model"])
        else:
            cfg.update(patch)
        with open(os.path.join(cfgs, name + ".json"), "w") as f:
            json.dump(cfg, f)
    workloads = []
    for cell, (config, traffic, patch) in CELLS.items():
        with open(os.path.join(root, "bench", "traffic", traffic + ".json")) as f:
            tr = json.load(f)
        tr.update(patch)
        with open(os.path.join(root, "bench", "traffic", traffic + ".json"), "w") as f:
            json.dump(tr, f)
        real = next(w for w in bench["workloads"] if w["traffic"] == traffic)
        with open(os.path.join(root, "bench", "cells", cell + ".json"), "w") as f:
            json.dump({"limits": LIMITS[cell]}, f)
        workloads.append({**real, "name": cell})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real["name"] in m.get("workloads", []):
                m["workloads"].append(cell)
    bench["workloads"] = workloads
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
