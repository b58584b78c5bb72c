"""End-to-end behaviour: train -> checkpoint -> kill -> resume -> serve,
all on the RawArray data plane (the paper's contribution as a system)."""

import os
import signal

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.store import latest_step
from repro.configs import get_config
from repro.data import DataLoader, RaDataset, make_token_dataset
from repro.distributed.optimizer import AdamWConfig
from repro.models import build_model
from repro.serving import ServeEngine
from repro.train import TrainLoopConfig, train

TINY = get_config("paper_lm").with_(
    n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128, vocab=256, max_seq=64
)


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("sys") / "ds")
    make_token_dataset(root, n_docs=256, seq_len=32, vocab=TINY.vocab, shard_rows=64)
    return root


def _loop(tmp, steps, ckpt_every=5):
    return TrainLoopConfig(
        steps=steps, ckpt_every=ckpt_every, ckpt_dir=tmp, log_every=1000,
        adamw=AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=200),
    )


def test_train_reduces_loss_and_checkpoints(dataset, tmp_path):
    model = build_model(TINY)
    loader = DataLoader(RaDataset(dataset), 8, seed=0)
    out = train(model, loader, _loop(str(tmp_path / "ck"), 30), resume=False)
    assert out["steps"] == 30
    assert np.mean(out["losses"][-5:]) < np.mean(out["losses"][:5])
    assert latest_step(str(tmp_path / "ck")) == 30


def test_resume_continues_identically(dataset, tmp_path):
    """Train 20 straight vs 10 + resume + 10: identical final params."""
    ck1, ck2 = str(tmp_path / "a"), str(tmp_path / "b")
    model = build_model(TINY)

    out_straight = train(
        model, DataLoader(RaDataset(dataset), 8, seed=1), _loop(ck1, 20, ckpt_every=10),
        resume=False,
    )
    train(
        model, DataLoader(RaDataset(dataset), 8, seed=1), _loop(ck2, 10, ckpt_every=10),
        resume=False,
    )
    out_resumed = train(
        model, DataLoader(RaDataset(dataset), 8, seed=1), _loop(ck2, 20, ckpt_every=10),
        resume=True,
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(out_straight["params"]),
        jax.tree_util.tree_leaves(out_resumed["params"]),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_preemption_checkpoint_and_restart(dataset, tmp_path):
    """SIGTERM mid-run -> checkpoint flushed; restart resumes past it."""
    ck = str(tmp_path / "ck")
    model = build_model(TINY)
    sent = {"n": 0}

    def bomb(step, metrics):
        if step == 7 and not sent["n"]:
            sent["n"] = 1
            os.kill(os.getpid(), signal.SIGTERM)

    out = train(
        model, DataLoader(RaDataset(dataset), 8, seed=2), _loop(ck, 50),
        resume=False, hooks=[bomb],
    )
    assert out["preempted"]
    assert out["steps"] < 50
    saved = latest_step(ck)
    assert saved is not None and saved >= 7
    out2 = train(model, DataLoader(RaDataset(dataset), 8, seed=2), _loop(ck, saved + 5))
    assert out2["steps"] == saved + 5 and not out2["preempted"]


def test_serve_from_trained_checkpoint(dataset, tmp_path):
    ck = str(tmp_path / "ck")
    model = build_model(TINY)
    train(model, DataLoader(RaDataset(dataset), 8, seed=0), _loop(ck, 10), resume=False)
    step = latest_step(ck)
    engine = ServeEngine(model, checkpoint=os.path.join(ck, f"step_{step:08d}"))
    prompts = np.random.default_rng(0).integers(1, TINY.vocab, (4, 8)).astype(np.int32)
    out = engine.generate(prompts, max_new=8)
    assert out.shape == (4, 8)
    assert np.all((out >= 0) & (out < TINY.vocab))
    # greedy decode must equal the full-prefill oracle
    seq = prompts.copy()
    params = engine.params
    for _ in range(8):
        logits, _ = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(seq)})
        seq = np.concatenate([seq, np.asarray(jnp.argmax(logits, -1))[:, None].astype(np.int32)], 1)
    assert np.array_equal(out, seq[:, 8:])


def test_loader_prefetch_overlaps(dataset):
    """The loader must not starve the consumer (paper's latency story)."""
    import time

    loader = DataLoader(RaDataset(dataset), 8, seed=0, prefetch=4)
    next(loader)
    time.sleep(0.05)  # let prefetch fill
    t0 = time.perf_counter()
    for _ in range(8):
        next(loader)
        time.sleep(0.01)  # simulate compute
    waited = loader.stats()["loader_wait_s"]
    loader.stop()
    assert waited < 0.05


def test_resume_restores_into_shapes_not_device_buffers(dataset, tmp_path):
    """With a checkpoint present, train() initialises nothing on the device:
    the restore targets come from jax.eval_shape, so resuming holds one
    copy of the training state, not two."""
    ck = str(tmp_path / "ck")
    model = build_model(TINY)
    train(model, DataLoader(RaDataset(dataset), 8, seed=4), _loop(ck, 5), resume=False)

    concrete_inits = []
    real_init = model.init

    def spy(key):
        concrete_inits.append(not isinstance(key, jax.core.Tracer))
        return real_init(key)

    model.init = spy
    out = train(model, DataLoader(RaDataset(dataset), 8, seed=4), _loop(ck, 7))
    assert out["steps"] == 7 and len(out["losses"]) == 2
    assert concrete_inits and not any(concrete_inits)


def test_compile_cache_dir_is_fixed_or_from_env(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.delenv(compile_cache.ENV, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.cache_dir() == os.path.join(repo, ".jax_cache")
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
    assert compile_cache.cache_dir() == "/elsewhere/cache"
    # with the variable set JAX already reads it: the helper sets nothing
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("child", [
    "import repro.remote.server",  # benchmarks/bench_coldstart.py's origin
    # benchmarks/bench_mesh.py's worker
    "from repro.data import DataLoader, RaDataset; from repro.distributed.data_mesh import DataMesh",
])
def test_spawned_children_do_not_import_jax(child):
    """A chip belongs to one process: the processes benchmarks spawn beside
    a JAX parent must never load JAX themselves."""
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    code = f"import sys; {child}; sys.exit(1 if 'jax' in sys.modules else 0)"
    env = dict(os.environ, PYTHONPATH=src)
    assert subprocess.run([sys.executable, "-c", code], env=env, timeout=60).returncode == 0
