"""Readings that set a cell's limits from above: the control (the reference
put in the program's place, computed in the next precision down), read
against the reference at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

Prints one JSON line per seed with each compared number, then a summary
line with the smallest reading of each. The benchmark's own runs never
run this. Exits non-zero without a TPU, like ``bench/run.py``.

* coldstart: the weights decoded from 4-bit codes over the same per-channel
  range, against their u8 decode.
* feed: the seed's images held in bfloat16, against float32.
"""

from __future__ import annotations

import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _coldstart(cell, ref, seed: int) -> dict:
    import jax

    from bench.generators import coldstart as generator

    params = jax.jit(lambda: ref.init(cell.config, seed))()
    weights = {n: np.asarray(a) for n, a in params.items()}
    del params
    decoded = {}
    with ThreadPoolExecutor(8) as pool:
        for n, w in weights.items():
            w2 = generator.views(w)
            lo, hi = generator.channel_range(w2, pool)
            parts = pool.map(lambda i: generator.decode(w2[i:i + generator.ROWS].astype(np.float32), lo, hi, 4),
                             range(0, len(w2), generator.ROWS))
            decoded[n] = np.concatenate(list(parts)).astype(w.dtype).reshape(w.shape)
    return {"leaf_err": generator.compare(decoded, weights)}


def _feed(cell, ref, seed: int) -> dict:
    import jax.numpy as jnp

    from bench.generators import feed as generator

    cfg, tr = cell.config, cell.traffic
    rng = np.random.default_rng([seed % (1 << 64), 1])
    batch = tr["batch"]
    low = ref.Rows(cfg, seed, dtype=jnp.bfloat16)
    kept = []
    for _ in range(tr["sampled_batches"]):
        rows = rng.choice(cfg["images"], batch, replace=False)
        kept.append((np.stack([low.image(r) for r in rows]), ref.labels(cfg, rows), batch))
    return generator.compare(kept, cfg, seed, ref)


READINGS = {"coldstart": _coldstart, "feed": _feed}


def main(argv=None, *, require_tpu: bool = True, root: str = ROOT) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    args = p.parse_args(argv)
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import harness

    cell = harness.resolve(args.workload, root)
    if require_tpu:
        try:
            harness.require_chips(cell.chips)
        except harness.NoChip as e:
            print(f"control: {e}; not running", file=sys.stderr)
            return 3
    ref = harness.load_module(cell.reference_file)
    reader = READINGS[cell.traffic["generator"]]
    readings = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        r = reader(cell, ref, seed)
        readings.append(r)
        print(json.dumps({"seed": seed, **r,
                          "seconds": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"smallest": {k: min(r[k] for r in readings) for k in readings[0]}}), flush=True)
    return 0


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    raise SystemExit(main())
