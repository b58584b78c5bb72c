"""Feed traffic: ``DeviceLoader(DataLoader(RaDataset))`` over a u8-quantized
image set, consumed with no step.

Parameters (``bench/traffic/<mix>.json``): ``batch``, ``warmup_batches``
and ``sampled_batches`` (how many of the window's first epoch are compared,
drawn from the seed).

Each batch of the window is waited for on the device (``block_until_ready``)
and dropped; the sampled ones, and the window's last, are kept on the device
and compared with the reference once the window has closed.
"""

from __future__ import annotations

import os
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def write_dataset(root: str, cfg: dict, seed: int, ref, workers: int = 4) -> None:
    """The seed's image set, through the program's ingest, one shard-sized
    piece of float values at a time: ``workers`` threads make the next
    pieces while ``DatasetBuilder`` quantizes and writes the current one."""
    from repro.core import QuantInfo
    from repro.data import DatasetBuilder

    scale, bias = ref.scale_bias(cfg)
    b = DatasetBuilder(root, {"image": (tuple(cfg["image"]), cfg["out_dtype"]), "label": ((), "int32")},
                       shard_rows=cfg["shard_rows"],
                       quantize={"image": QuantInfo(scale=scale, bias=bias, orig_dtype=cfg["out_dtype"])})

    def piece(block):
        codes = ref.block_codes(cfg, seed, block)
        rows = np.arange(block * cfg["shard_rows"], block * cfg["shard_rows"] + len(codes))
        return ref.dequant(codes, cfg), ref.labels(cfg, rows)

    def write(future):
        image, label = future.result()
        b.append(image=image, label=label)

    with ThreadPoolExecutor(workers) as pool:
        pending = deque()
        for block in range(-(-cfg["images"] // cfg["shard_rows"])):
            pending.append(pool.submit(piece, block))
            if len(pending) > workers:
                write(pending.popleft())
        while pending:
            write(pending.popleft())
    b.finish()


def compare(kept, cfg: dict, seed: int, ref, dtype=np.float32) -> dict:
    """Each kept batch against the images its labels name: the worst
    distance in quantization steps, and how many rows repeat in a batch."""
    rows = ref.Rows(cfg, seed, dtype)
    worst, repeated = 0.0, 0
    for images, labels, want_rows in kept:
        if len(images) != want_rows or len(labels) != want_rows:
            return {"image_err": float("inf"), "rows_repeated": 0.0}
        seen = set()
        for img, lab in zip(images, labels):
            # the row is the candidate nearest on a prefix; the whole image
            # is then compared with that row
            flat = img.reshape(-1)[:1023]
            best_row = min(rows.candidates(lab), default=None,
                           key=lambda r: ref.steps(flat, rows.image(r).reshape(-1)[:1023], cfg))
            best = float("inf") if best_row is None else ref.steps(img, rows.image(best_row), cfg)
            worst = max(worst, best)
            repeated += best_row in seen
            seen.add(best_row)
    return {"image_err": worst, "rows_repeated": float(repeated)}


def run(run) -> dict:
    import jax

    from repro.data import DataLoader, DeviceLoader, RaDataset

    cfg, tr = run.cell.config, run.cell.traffic
    ref = run.reference()
    root = os.path.join(run.workdir, "images")
    write_dataset(root, cfg, run.seed, ref)

    batch = tr["batch"]
    loader = DeviceLoader(DataLoader(RaDataset(root), batch, seed=run.seed))
    rng = np.random.default_rng([run.seed % (1 << 64), 1])
    epoch = loader.steps_per_epoch()
    sampled = set(rng.choice(epoch, size=min(tr["sampled_batches"], epoch), replace=False).tolist())
    run.counters["t_data_s"] = time.perf_counter() - run.t_process
    try:
        for _ in range(tr["warmup_batches"]):
            jax.block_until_ready([next(loader)[k] for k in ("image", "label")])
        s0 = loader.stats()
        kept, n = {}, 0
        run.window_start()
        while True:
            with run.span("feed.next"):
                b = next(loader)
            with run.span("feed.ready"):
                jax.block_until_ready([b["image"], b["label"]])
            if n in sampled:
                kept[n] = b
            n += 1
            if time.perf_counter() - run.t_window >= run.seconds:
                break
        run.window_end()
        kept[n - 1] = b
        del b
        s1 = loader.stats()
    finally:
        loader.stop()

    d = {k: s1[k] - s0[k] for k in ("loader_produce_s", "batches", "h2d_s", "h2d_batches",
                                     "device_wait_s")}
    run.counters.update({"batches": n, "images": n * batch,
                         **{k + "_window": v for k, v in d.items()}})
    run.attempted, run.failed = n, 0

    host = [(np.asarray(b["image"]), np.asarray(b["label"]), batch) for _, b in sorted(kept.items())]
    del kept
    result = compare(host, cfg, run.seed, ref)
    run.check("image_err", result["image_err"])
    run.check("rows_repeated", result["rows_repeated"])
    return {"feed_images_per_s": n * batch / run.window_s}
