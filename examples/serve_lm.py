"""Serve a trained checkpoint with batched decode requests.

    PYTHONPATH=src python examples/serve_lm.py            # uses train_lm ckpt
    PYTHONPATH=src python examples/serve_lm.py --random   # random weights

Weights are memory-mapped straight from the RawArray checkpoint — cold
start is header-parse + page-touch, not a full deserialize (the paper's
mmap story as serving-cold-start latency).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--workdir", default="/tmp/ra_train_lm")
    p.add_argument("--arch", default="paper_lm")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--random", action="store_true")
    args = p.parse_args()

    import jax

    from repro.checkpoint.store import latest_step
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import ServeEngine

    cfg = get_config(args.arch)
    model = build_model(cfg)

    ckpt_dir = os.path.join(args.workdir, "ckpt")
    step = None if args.random else latest_step(ckpt_dir)
    t0 = time.perf_counter()
    if step is None:
        print("[serve] no checkpoint; random init")
        engine = ServeEngine(model, model.init(jax.random.PRNGKey(0)))
    else:
        path = os.path.join(ckpt_dir, f"step_{step:08d}")
        print(f"[serve] mmap-loading checkpoint {path}")
        engine = ServeEngine(model, checkpoint=path)
    print(f"[serve] weights ready in {time.perf_counter()-t0:.3f}s")

    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab, (args.batch, 16)).astype(np.int32)
    t0 = time.perf_counter()
    out = engine.generate(prompts, max_new=args.max_new)
    dt = time.perf_counter() - t0
    print(f"[serve] generated {out.shape} tokens; sample row: {out[0][:16]}")
    print(f"[serve] {out.size / dt:.1f} tokens/s (compile included)")


if __name__ == "__main__":
    main()
