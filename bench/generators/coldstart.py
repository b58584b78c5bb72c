"""Cold-start traffic: a serving replica boots from a checkpoint, again and
again, each time cold.

Parameters (``bench/traffic/<mix>.json``): ``chunked``, ``quantize`` and
``codec`` (how ``save_checkpoint`` stores the checkpoint), and ``warmup``
(cold starts in set-up). The checkpoint is served over a loopback URL by
``python -m repro.remote.server`` in a child process that does not import
JAX, so the replica's origin shares neither its process nor its GIL.

The weights are the configuration's, made from the seed on the device in
one jitted call. Each cold start of the window runs ``_cold()`` (pooled
readers and the shared block cache dropped), deletes the previous
replica's arrays, then times ``ServeEngine(model, checkpoint=...)`` until
every leaf is ready on the device. The last replica's leaves are compared
with the reference's own quantization of the same weights.
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _cold() -> None:
    from repro import remote

    remote.close_readers()
    remote.reset_shared_cache()


def _spawn_server(root: str, src: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen([sys.executable, "-m", "repro.remote.server", root, "--port", "0"],
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
    for _ in range(50):
        line = proc.stdout.readline()
        if not line:
            break
        m = re.search(r"at (http://[0-9.]+:[0-9]+)", line)
        if m:
            return proc, m.group(1)
    proc.kill()
    proc.wait()
    raise RuntimeError("checkpoint server failed to start")


ROWS = 1024  # rows of a (rows, channels) leaf view handled at a time


def channel_range(w2: np.ndarray, pool) -> tuple:
    """Per-channel float32 (min, max) of a (rows, channels) leaf view."""
    def part(i):
        x = w2[i:i + ROWS].astype(np.float32)
        return x.min(axis=0), x.max(axis=0)

    parts = list(pool.map(part, range(0, len(w2), ROWS)))
    return np.min([p[0] for p in parts], axis=0), np.max([p[1] for p in parts], axis=0)


def decode(x: np.ndarray, lo: np.ndarray, hi: np.ndarray, bits: int = 8) -> np.ndarray:
    """The plain quantization of float32 rows ``x`` as the format states it:
    per channel, ``[lo, hi]`` onto ``[0, 2**bits - 1]``,
    ``code = rint((x - lo) / scale)``, decoded in float32 as
    ``code * scale + lo``. ``bits`` is 8 for the reference; fewer is the
    control: fewer levels over the same range."""
    levels = np.float32(2 ** bits - 1)
    scale = np.where(hi > lo, (hi - lo) / levels, np.float32(1.0)).astype(np.float32)
    code = np.clip(np.rint((x - lo) / scale), 0, levels)
    return code.astype(np.float32) * scale + lo


def views(w: np.ndarray) -> np.ndarray:
    """A leaf as (rows, channels): channels are its last axis; a 1-D leaf
    is one channel."""
    return w.reshape(-1, w.shape[-1] if w.ndim >= 2 else 1)


def compare(got: dict, want: dict, workers: int = 8) -> float:
    """Worst distance over all leaves of ``got`` from the u8 decode of
    ``want``, in units of each channel's u8 scale."""
    worst = 0.0
    with ThreadPoolExecutor(workers) as pool:
        for name, w in want.items():
            g = got.get(name)
            if g is None or g.shape != w.shape or g.dtype != w.dtype:
                return float("inf")
            g2, w2 = views(g), views(w)
            lo, hi = channel_range(w2, pool)
            scale = np.where(hi > lo, (hi - lo) / np.float32(255.0), np.float32(1.0)).astype(np.float32)

            def part(i):
                want_dec = decode(w2[i:i + ROWS].astype(np.float32), lo, hi).astype(w.dtype)
                err = np.abs(g2[i:i + ROWS].astype(np.float32) - want_dec.astype(np.float32))
                return float(np.max(err / scale))

            worst = max([worst, *pool.map(part, range(0, len(w2), ROWS))])
    return worst


def run(run) -> dict:
    import jax

    from repro.checkpoint import save_checkpoint
    from repro.checkpoint.coldstart import ColdStartStats
    from repro.serving import engine as serving_engine

    from bench.common import leaf_names, model_of, nest

    cfg, tr = run.cell.config, run.cell.traffic
    ref = run.reference()
    params = jax.jit(lambda: ref.init(cfg, run.seed))()
    weights = {n: np.asarray(a) for n, a in params.items()}
    del params
    root = os.path.join(run.workdir, "ckpt")
    ckpt = save_checkpoint(root, 0, nest(weights), chunked=tr["chunked"], codec=tr.get("codec"),
                           quantize=tr["quantize"])
    run.counters["t_data_s"] = time.perf_counter() - run.t_process
    proc, base = _spawn_server(root, os.path.join(run.cell.root, "src"))
    url = f"{base}/{os.path.basename(ckpt)}"
    model = model_of(cfg)

    # the engine's restore, with a stats object handed in
    stats = []
    restore = serving_engine.restore_pipelined

    def restore_with_stats(*a, **kw):
        st = ColdStartStats()
        stats.append(st)
        return restore(*a, stats=st, **kw)

    serving_engine.restore_pipelined = restore_with_stats
    try:
        def boot():
            _cold()
            with run.span("coldstart.restore"):
                eng = serving_engine.ServeEngine(model, checkpoint=url)
                jax.block_until_ready(eng.params)
            return eng

        for _ in range(tr["warmup"]):
            eng = boot()
            for a in jax.tree_util.tree_leaves(eng.params):
                a.delete()
            del eng
        stats.clear()
        run.counters["t_warm_s"] = time.perf_counter() - run.t_process
        times = []
        run.window_start()
        while True:
            t0 = time.perf_counter()
            eng = boot()
            times.append(time.perf_counter() - t0)
            if time.perf_counter() - run.t_window >= run.seconds:
                break
            with run.span("coldstart.release"):
                for a in jax.tree_util.tree_leaves(eng.params):
                    a.delete()
                del eng
        run.window_end()
    finally:
        serving_engine.restore_pipelined = restore
        proc.terminate()
        proc.wait()

    restored = {n: np.asarray(a) for n, a in leaf_names(eng.params).items()}
    for a in jax.tree_util.tree_leaves(eng.params):
        a.delete()
    del eng

    run.counters.update({
        "cold_starts": len(times),
        "restore_total_s": sum(times),
        "restore_h2d_s_total": sum(s.h2d_s for s in stats),
        "dequant_leaves_per_start": stats[-1].dequant_leaves,
    })
    run.attempted, run.failed = len(times), 0
    run.check("leaf_err", compare(restored, weights))
    return {"coldstart_s": sum(times) / len(times)}
