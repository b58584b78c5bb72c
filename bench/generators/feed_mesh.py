"""Data-parallel feed traffic: ``DeviceLoader(DataLoader(RaDataset, mesh=
DataMesh("host0", ["host0"])))`` over a u8-quantized image set, consumed
with no step. Each batch is one global ``jax.Array`` per field, sharded
over every device of the process (``PartitionSpec("data")``), assembled by
the program.

Parameters (``bench/traffic/<mix>.json``): ``batch`` (the global batch:
``batch / chips`` rows a chip), ``warmup_batches`` and ``sampled_batches``
(how many of the window's first ``steps_per_epoch`` batches are compared,
drawn from the seed).

Each global batch of the window is waited for on the device
(``block_until_ready``) and dropped, as in the one-chip feed (``feed.py``,
whose ``write_dataset`` makes the image set); the sampled ones and the
window's last are kept on the device, and so are the label arrays of the
last whole epoch inside the window (found from each batch's ``_state``).
After the window they are read shard by shard (``addressable_shards``)
and compared with the reference.
"""

from __future__ import annotations

import os
import time

import numpy as np

from bench.generators.feed import write_dataset


def read_shards(b: dict, devices: list) -> list:
    """One global batch as the reference takes it: per image shard, its
    chip (position in ``devices``), the first global row of its image and
    of its label shard, and both as numpy arrays."""
    labels = {s.device: s for s in b["label"].addressable_shards}
    out = []
    for s in b["image"].addressable_shards:
        lab = labels.get(s.device)
        out.append((devices.index(s.device), s.index[0].start or 0,
                    -1 if lab is None else lab.index[0].start or 0,
                    np.asarray(s.data),
                    np.zeros(0, np.int32) if lab is None else np.asarray(lab.data)))
    return out


def run(run) -> dict:
    import jax

    from repro.core import obs
    from repro.data import DataLoader, DeviceLoader, RaDataset
    from repro.distributed.data_mesh import DataMesh

    cfg, tr = run.cell.config, run.cell.traffic
    ref = run.reference()
    root = os.path.join(run.workdir, "images")
    write_dataset(root, cfg, run.seed, ref)

    batch = tr["batch"]
    devices = jax.devices()
    loader = DeviceLoader(DataLoader(RaDataset(root), batch, seed=run.seed,
                                     mesh=DataMesh("host0", ["host0"])))
    rng = np.random.default_rng([run.seed % (1 << 64), 1])
    epoch = loader.steps_per_epoch()
    sampled = set(rng.choice(epoch, size=min(tr["sampled_batches"], epoch), replace=False).tolist())
    shards = obs.counter("device_loader.shards")
    run.counters["t_data_s"] = time.perf_counter() - run.t_process
    try:
        for _ in range(tr["warmup_batches"]):
            jax.block_until_ready([next(loader)[k] for k in ("image", "label")])
        s0, shards0 = loader.stats(), shards.value
        kept, n = {}, 0
        epochs, whole = {}, None  # label arrays by epoch, then step; the last whole epoch's
        run.window_start()
        while True:
            with run.span("feed.next"):
                b = next(loader)
            with run.span("feed.ready"):
                jax.block_until_ready([b["image"], b["label"]])
            if n in sampled:
                kept[n] = b
            state = b["_state"]
            steps = epochs.setdefault(state.epoch, {})
            steps[state.step] = b["label"]
            if len(steps) == epoch:
                whole = steps
                epochs = {}
            n += 1
            if time.perf_counter() - run.t_window >= run.seconds:
                break
        run.window_end()
        kept[n - 1] = b
        del b, epochs
        s1, shards1 = loader.stats(), shards.value
    finally:
        loader.stop()

    d = {k: s1[k] - s0[k] for k in ("loader_produce_s", "batches", "h2d_s", "h2d_batches",
                                     "device_wait_s")}
    run.counters.update({"batches": n, "images": n * batch, "shards_window": shards1 - shards0,
                         **{k + "_window": v for k, v in d.items()}})
    run.attempted, run.failed = n, 0

    rows = ref.Rows(cfg, run.seed)
    worst = {"image_err": 0.0, "rows_repeated": 0.0, "shard_misplaced": 0.0}
    for i in sorted(kept):
        result = ref.compare_batch(read_shards(kept.pop(i), devices), rows, cfg,
                                   len(devices), batch)
        worst = {k: max(v, result[k]) for k, v in worst.items()}
    for k, v in worst.items():
        run.check(k, v)
    run.check("epoch_rows_wrong", float("inf") if whole is None else
              ref.epoch_rows_wrong([np.asarray(whole[s]) for s in sorted(whole)], cfg))
    return {"feed_images_per_s": n * batch / run.window_s}
