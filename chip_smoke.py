"""Chip smoke test: the data plane's main path once, on TPU, at OLMo-1B width.

    python chip_smoke.py [--seed N]     # one chip: train, resume, cold start + serve, feed
    python chip_smoke.py --chips 4      # only the data-parallel mesh phase, on four chips

Everything runs in this one process (the loopback checkpoint server is a
thread), on data generated from ``--seed`` in ``.chip_smoke/`` at the root
of the checkout, which is removed at the end. Phases, in order:

1. device  -- refuse to run unless ``jax.devices()[0]`` is a TPU;
2. train   -- OLMo-1B at its published widths (random weights), batch 8 x 256
              tokens, AdamW with int8 moments, fed by
              ``DeviceLoader(DataLoader(...))`` through ``repro.train.train``,
              checkpoint at the last step; every loss finite;
3. resume  -- ``train`` again on the same workdir: ``restore_pipelined`` from
              that checkpoint, then two more steps;
4. serve   -- save the parameters chunked and u8-quantized, serve them over
              HTTP, boot ``ServeEngine`` from the URL (the compiled Pallas
              dequant at every leaf width), check every leaf against the host
              dequant, answer 8 prompts x 16 new tokens and match an engine
              holding the host-dequantized weights token for token;
5. feed    -- ``DeviceLoader`` over a u8-quantized CIFAR-shaped field
              (32x32x3, batch 256) against ``DataLoader(dequant=True)``.

With ``--chips 4`` the only phase is the mesh: a data-parallel OLMo-1B step
fed by a global-array ``DeviceLoader`` over a one-host ``DataMesh``,
against the same global batch on one device, plus a sharded
``restore_pipelined`` against the one-device restore.

Per-phase wall time and HBM use go on earlier lines. A failed check raises,
so the script exits non-zero; the last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SEQ = 256
BATCH = 8
TRAIN_STEPS = 3
RESUME_STEPS = 2
PROMPTS, PROMPT_LEN, NEW_TOKENS = 8, 32, 16
IMAGE = (32, 32, 3)
IMAGE_BATCH = 256
# first-step loss, data-parallel vs one device: a few bf16 ulps (2^-8 each)
LOSS_RTOL = 1e-2


def check(ok: bool, what: str) -> None:
    """``assert`` that survives ``python -O``."""
    if not ok:
        raise AssertionError(what)


def report(name: str, t0: float) -> None:
    print(f"[phase] {name} wall_s={time.perf_counter() - t0} {hbm()}", flush=True)


def adamw():
    """AdamW with int8 moments (float32 ones do not fit one chip's HBM
    beside OLMo-1B), on its default warmup: a run's first steps."""
    from repro.distributed.optimizer import AdamWConfig

    return AdamWConfig(moment_dtype="int8")


def hbm() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return (f"hbm_peak_bytes={stats.get('peak_bytes_in_use', 'not reported')} "
            f"hbm_in_use_bytes={stats.get('bytes_in_use', 'not reported')}")


def token_dataset(workdir: str, cfg, seed: int, n_docs: int = 512) -> str:
    from repro.data import make_token_dataset

    root = os.path.join(workdir, "tokens")
    if not os.path.exists(os.path.join(root, "manifest.json")):
        make_token_dataset(root, n_docs=n_docs, seq_len=SEQ, vocab=cfg.vocab,
                           seed=seed, shard_rows=64)
    return root


def image_dataset(workdir: str, seed: int, n: int) -> str:
    from repro.data import DatasetBuilder

    root = os.path.join(workdir, "images")
    rng = np.random.default_rng(seed)
    b = DatasetBuilder(root, {"image": (IMAGE, "float32"), "label": ((), "int32")},
                       shard_rows=IMAGE_BATCH, quantize={"image": "u8"})
    b.append(image=rng.random((n,) + IMAGE, dtype=np.float32),
             label=rng.integers(0, 10, n).astype(np.int32))
    b.finish()
    return root


def same_as_host_dequant(dev: np.ndarray, host: np.ndarray, what: str) -> None:
    """A device-dequantized float batch against the host's numpy dequant of
    the same codes: the same float32 ``q*scale + bias``."""
    check(dev.dtype == host.dtype and dev.shape == host.shape,
          f"{what}: {dev.dtype}{dev.shape} vs host {host.dtype}{host.shape}")
    diff = np.abs(dev.astype(np.float64) - host.astype(np.float64))
    ulp = np.spacing(np.abs(host).astype(np.float32)).astype(np.float64)
    print(f"[check] {what}: exact={float(np.mean(dev == host))} "
          f"max_ulp={float((diff / ulp).max())}", flush=True)
    check(bool((diff <= ulp).all()), f"{what}: device dequant differs from host by more than 1 ulp")


# --------------------------------------------------------------- one chip
def phase_train(model, workdir: str, seed: int, steps: int) -> dict:
    """``repro.train.train`` fed by ``DeviceLoader``; resumes when the
    workdir already holds a checkpoint. Returns ``train``'s summary."""
    from repro.data import DataLoader, DeviceLoader, RaDataset
    from repro.train import TrainLoopConfig, train

    ds = token_dataset(workdir, model.cfg, seed)
    loader = DeviceLoader(DataLoader(RaDataset(ds), BATCH, seed=seed))
    out = train(
        model, loader,
        TrainLoopConfig(steps=steps, ckpt_every=steps, log_every=1, adamw=adamw(),
                        ckpt_dir=os.path.join(workdir, "ckpt")),
        init_rng=seed,
        hooks=[lambda step, _: print(f"[train] after step {step}: {hbm()}", flush=True)],
    )
    print(f"[train] losses={out['losses']} steps={out['steps']}", flush=True)
    check(out["steps"] == steps, f"trained to step {out['steps']}, wanted {steps}")
    check(all(np.isfinite(out["losses"])), "non-finite loss")
    # random weights in warmup: the loss stays near ln(vocab); far above it
    # the step is broken
    check(max(out["losses"]) < 1.5 * np.log(model.cfg.vocab), f"loss blew up: {out['losses']}")
    return out


def phase_serve(model, params, workdir: str, seed: int) -> None:
    """Quantized checkpoint -> HTTP -> ``ServeEngine`` cold start -> generate."""
    import jax

    from repro import remote
    from repro.checkpoint import load_checkpoint, save_checkpoint
    from repro.serving import ServeEngine

    root = os.path.join(workdir, "serve")
    ckpt = save_checkpoint(root, 0, params, chunked=True, quantize="u8")
    like = jax.eval_shape(lambda: params)
    host, _, _ = load_checkpoint(ckpt, like)  # the host (numpy) dequant
    with open(os.path.join(ckpt, "manifest.json")) as f:
        leaves = json.load(f)["leaves"]
    check(all("quant" in e for e in leaves.values()), "a parameter leaf was stored unquantized")

    server = remote.serve(root)
    try:
        t0 = time.perf_counter()
        engine = ServeEngine(model, checkpoint=f"{server.url}/{os.path.basename(ckpt)}")
        print(f"[serve] cold start from URL: {len(leaves)} leaves in "
              f"{time.perf_counter() - t0} s", flush=True)
    finally:
        server.shutdown()
    flat_dev = jax.tree_util.tree_flatten_with_path(engine.params)[0]
    flat_host = jax.tree_util.tree_leaves(host)
    for (path, dev), want in zip(flat_dev, flat_host):
        name = "param" + "".join(f"__{getattr(k, 'key', k)}" for k in path)
        scale = np.asarray(leaves[name]["quant"]["scale"], np.float64)
        got = np.asarray(dev)
        check(got.dtype == want.dtype and got.shape == want.shape, f"{name}: dtype/shape")
        err = np.abs(got.astype(np.float64) - want.astype(np.float64))
        print(f"[check] {name} {got.shape}: exact={float(np.mean(got == want))} "
              f"max_err_over_scale={float((err / scale).max())}", flush=True)
        check(bool((err <= scale / 2).all()), f"{name}: restored leaf outside the quantization bound")
    del flat_dev, flat_host

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, model.cfg.vocab, (PROMPTS, PROMPT_LEN)).astype(np.int32)
    t0 = time.perf_counter()
    out = engine.generate(prompts, NEW_TOKENS)
    print(f"[serve] generate {PROMPTS}x{NEW_TOKENS} tokens in {time.perf_counter() - t0} s "
          "(compile included)", flush=True)
    check(out.shape == (PROMPTS, NEW_TOKENS), f"generate returned {out.shape}")
    check(bool(((out >= 0) & (out < model.cfg.vocab)).all()), "token outside the vocabulary")
    # reference: the same engine on the host-dequantized weights. With
    # random weights 16 layers deep, rounding differences grow ~7x per layer,
    # so only the same computation on the same bytes is a fair reference
    ref = ServeEngine(model, params=jax.device_put(host)).generate(prompts, NEW_TOKENS)
    print(f"[check] tokens equal to the host-weights reference: "
          f"{float(np.mean(out == ref))}", flush=True)
    check(np.array_equal(out, ref), "URL-booted engine disagrees with the host-weights reference")


def phase_feed(workdir: str, seed: int, batches: int = 4) -> None:
    """On-device dequant of a quantized field against the host dequant."""
    from repro.data import DataLoader, DeviceLoader, RaDataset

    root = image_dataset(workdir, seed, IMAGE_BATCH * batches)
    dev = DeviceLoader(DataLoader(RaDataset(root), IMAGE_BATCH, seed=seed))
    host = DataLoader(RaDataset(root), IMAGE_BATCH, seed=seed, dequant=True)
    try:
        for i in range(batches):
            d, h = next(dev), next(host)
            check(d["_state"].__dict__ == h["_state"].__dict__, f"batch {i}: loader state")
            check(np.array_equal(np.asarray(d["label"]), h["label"]), f"batch {i}: labels")
            same_as_host_dequant(np.asarray(d["image"]), h["image"], f"feed batch {i}")
    finally:
        dev.stop()
        host.stop()
    print(f"[feed] {batches} batches of {IMAGE_BATCH}x{IMAGE} match the host dequant "
          f"({dev.stats()['h2d_bytes']} bytes moved)", flush=True)


def run_one_chip(cfg, workdir: str, seed: int) -> None:
    from repro.models import build_model

    model = build_model(cfg)
    t0 = time.perf_counter()
    out = phase_train(model, workdir, seed, TRAIN_STEPS)
    del out  # the resume must find the device empty of the first run's state
    report("train", t0)

    t0 = time.perf_counter()
    out = phase_train(model, workdir, seed, TRAIN_STEPS + RESUME_STEPS)
    check(len(out["losses"]) == RESUME_STEPS,
          f"resume took {len(out['losses'])} steps: it did not start at step {TRAIN_STEPS}")
    params = out["params"]
    del out
    report("resume", t0)

    t0 = time.perf_counter()
    phase_serve(model, params, workdir, seed)
    del params
    report("serve", t0)

    t0 = time.perf_counter()
    phase_feed(workdir, seed)
    report("feed", t0)


# ------------------------------------------------------------- four chips
def run_mesh(cfg, workdir: str, seed: int) -> None:
    """Data-parallel step and sharded restore over every local device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.checkpoint import restore_pipelined, save_checkpoint, shardings_from_specs
    from repro.data import DataLoader, DeviceLoader, RaDataset
    from repro.distributed import optimizer as optim
    from repro.distributed.data_mesh import DataMesh
    from repro.models import build_model

    t0 = time.perf_counter()
    devs = jax.devices()
    n = len(devs)
    model = build_model(cfg)
    mesh_of_one_host = DataMesh("host0", ["host0"])

    def on_every_device(a, what: str) -> None:
        shards = a.addressable_shards
        check(len(a.sharding.device_set) == n and {s.device for s in shards} == set(devs),
              f"{what}: shards on {sorted(str(s.device) for s in shards)}")

    # -- data-parallel step vs one device, same global batch ---------------
    loader = DeviceLoader(
        DataLoader(RaDataset(token_dataset(workdir, cfg, seed)), BATCH, seed=seed, mesh=mesh_of_one_host),
    )
    batch = next(loader)
    batch.pop("_state")
    loader.stop()
    on_every_device(batch["tokens"], "token batch")
    tokens = np.asarray(batch["tokens"])
    check(tokens.shape == (BATCH, SEQ), f"global batch {tokens.shape}")

    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    host_params = jax.device_get(params)
    ref_loss = float(jax.jit(lambda p, b: model.train_loss(p, b)[0])(
        params, {"tokens": jax.device_put(tokens, devs[0])}))
    del params

    replicated = NamedSharding(batch["tokens"].sharding.mesh, PartitionSpec())
    params = jax.device_put(host_params, replicated)
    cfg_opt = adamw()
    opt_state = jax.jit(lambda p: optim.init_state(p, cfg_opt), out_shardings=replicated)(params)

    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: model.train_loss(p, batch), has_aux=True)(params)
        params, opt_state, info = optim.apply_updates(params, grads, opt_state, cfg_opt)
        return params, opt_state, {**metrics, **info}

    params, opt_state, metrics = jax.jit(step, donate_argnums=(0, 1))(params, opt_state, batch)
    dp_loss = float(metrics["loss"])
    print(f"[mesh] first-step loss: {n}-device data parallel {dp_loss}, one device {ref_loss}",
          flush=True)
    check(bool(np.isfinite(dp_loss)), "non-finite data-parallel loss")
    check(abs(dp_loss - ref_loss) <= LOSS_RTOL * abs(ref_loss), "data-parallel loss disagrees")
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        on_every_device(leaf, f"updated param {jax.tree_util.keystr(path)}")
    del params, opt_state, metrics, batch
    report("mesh-step", t0)

    # -- sharded restore vs one-device restore -----------------------------
    t0 = time.perf_counter()
    ckpt = save_checkpoint(os.path.join(workdir, "mesh_ckpt"), 0, host_params)
    like = jax.eval_shape(lambda: host_params)
    one, _, _ = restore_pipelined(ckpt, like)
    mesh = replicated.mesh

    def split_first_divisible_axis(leaf):
        axis = next(i for i, d in enumerate(leaf.shape) if d % n == 0)
        return PartitionSpec(*([None] * axis), mesh.axis_names[0])

    specs = jax.tree_util.tree_map(split_first_divisible_axis, like)
    sharded, _, _ = restore_pipelined(ckpt, like, shardings=shardings_from_specs(mesh, specs))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(sharded)[0],
                            jax.tree_util.tree_leaves(one)):
        what = f"restored {jax.tree_util.keystr(path)}"
        on_every_device(a, what)
        check(np.asarray(a).tobytes() == np.asarray(b).tobytes(), f"{what}: bytes differ")
    print(f"[mesh] sharded restore over {n} devices is byte-identical to the one-device restore",
          flush=True)
    del one, sharded

    # -- quantized field: each shard dequantized on its own device ---------
    root = image_dataset(workdir, seed, IMAGE_BATCH)
    dev = DeviceLoader(DataLoader(RaDataset(root), IMAGE_BATCH, seed=seed, mesh=mesh_of_one_host))
    host = DataLoader(RaDataset(root), IMAGE_BATCH, seed=seed, mesh=mesh_of_one_host, dequant=True)
    try:
        d, h = next(dev), next(host)
    finally:
        dev.stop()
        host.stop()
    # assembly refuses a shard that is not on its own device, so this also
    # shows that every shard was dequantized where it lives
    on_every_device(d["image"], "image batch")
    same_as_host_dequant(np.asarray(d["image"]), h["image"], "mesh image batch")
    report("mesh-restore-feed", t0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the data-parallel mesh phase, on four chips")
    args = p.parse_args(argv)

    sys.path.insert(0, os.path.join(REPO, "src"))
    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}; not running", file=sys.stderr)
        return 1
    print(f"[device] platform={dev.platform} kind={dev.device_kind} count={len(devs)}", flush=True)
    print(f"[device] compile cache: {enable_compile_cache()}", flush=True)
    check(len(devs) >= args.chips, f"--chips {args.chips} but {len(devs)} devices")

    cfg = get_config("olmo_1b")
    workdir = os.path.join(REPO, ".chip_smoke", f"seed{args.seed}")
    shutil.rmtree(workdir, ignore_errors=True)
    try:
        if args.chips == 4:
            run_mesh(cfg, workdir, args.seed)
        else:
            run_one_chip(cfg, workdir, args.seed)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
