"""AdamW + int8 moments: convergence, schedules, quantization properties."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.distributed import optimizer as optim


def test_adamw_converges_quadratic():
    cfg = optim.AdamWConfig(lr=0.1, warmup_steps=10, total_steps=200, weight_decay=0.0)
    params = {"w": jnp.ones((4, 8)) * 3.0}
    state = optim.init_state(params, cfg)
    loss = lambda p: jnp.sum(jnp.square(p["w"] - 1.0))
    for _ in range(200):
        params, state, _ = optim.apply_updates(params, jax.grad(loss)(params), state, cfg)
    assert float(loss(params)) < 1e-5


def test_int8_moments_converge_close_to_fp32():
    loss = lambda p: jnp.sum(jnp.square(p["w"] - 1.0))
    outs = {}
    for mt in ("float32", "int8"):
        cfg = optim.AdamWConfig(lr=0.05, warmup_steps=5, total_steps=300, weight_decay=0.0, moment_dtype=mt)
        params = {"w": jnp.ones((2, 300)) * 3.0}
        state = optim.init_state(params, cfg)
        step = jax.jit(lambda p, s, g: optim.apply_updates(p, g, s, cfg))
        for _ in range(300):
            params, state, _ = step(params, state, jax.grad(loss)(params))
        outs[mt] = float(loss(params))
    assert outs["int8"] < 1e-2


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(1, 500),
    scale=st.floats(1e-4, 1e3),
)
def test_quantize_roundtrip_error_bound(n, scale):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=(3, n)) * scale, jnp.float32)
    q = optim.quantize_blockwise(x)
    y = optim.dequantize_blockwise(q, n)
    assert y.shape == x.shape
    # absmax int8: error <= blockmax/127 per element
    blocks = np.asarray(jnp.abs(x))
    err = np.abs(np.asarray(x - y))
    bound = blocks.max() / 127.0 * 1.001 + 1e-12
    assert err.max() <= bound


def test_quantized_state_is_small():
    params = {"w": jnp.zeros((1024, 1024), jnp.float32)}
    s8 = optim.init_state(params, optim.AdamWConfig(moment_dtype="int8"))
    s32 = optim.init_state(params, optim.AdamWConfig())
    b8 = sum(x.nbytes for x in jax.tree_util.tree_leaves(s8))
    b32 = sum(x.nbytes for x in jax.tree_util.tree_leaves(s32))
    assert b8 < 0.3 * b32  # ~4x smaller moments


def test_lr_schedule_shape():
    cfg = optim.AdamWConfig(lr=1.0, warmup_steps=100, total_steps=1000, min_lr_frac=0.1)
    lrs = [float(optim._lr_at(jnp.asarray(s), cfg)) for s in (1, 50, 100, 500, 1000, 2000)]
    assert lrs[0] < lrs[1] < lrs[2]          # warmup rising
    assert abs(lrs[2] - 1.0) < 0.02          # peak at warmup end
    assert lrs[3] < lrs[2]                   # decaying
    assert abs(lrs[4] - 0.1) < 0.02          # floor
    assert abs(lrs[5] - 0.1) < 0.02          # clamped after end


def test_grad_clip_bounds_update():
    cfg = optim.AdamWConfig(lr=1e-2, grad_clip=1.0, weight_decay=0.0, warmup_steps=0)
    params = {"w": jnp.zeros((4,))}
    state = optim.init_state(params, cfg)
    huge = {"w": jnp.full((4,), 1e9)}
    p2, _, info = optim.apply_updates(params, huge, state, cfg)
    assert float(info["grad_norm"]) > 1e8
    assert float(jnp.max(jnp.abs(p2["w"]))) < 1.0  # clipped step stays sane


def test_int8_second_moment_never_decodes_below_its_value():
    """Rounded to nearest, a small v in a block with a large one decoded as
    0 and the next update divided by eps alone; rounded up it cannot."""
    v = jnp.asarray([[1e-8, 3e-6, 1e-4] + [0.0] * 125], jnp.float32)
    up = optim.dequantize_blockwise(optim.quantize_blockwise(v, round_up=True), 128)
    assert bool(jnp.all(up >= v))
    assert bool(jnp.all((up == 0) == (v == 0)))


def test_int8_moments_track_fp32_on_a_transformer(tmp_path):
    """A reduced OLMo-1B trained a few steps at a real learning rate: the
    int8-moment loss stays with the float32 one (round-to-nearest second
    moments blew it from 6.7 to 184 in six steps)."""
    from repro.configs import get_config
    from repro.data import DataLoader, RaDataset, make_token_dataset
    from repro.models import build_model
    from repro.train import TrainLoopConfig, train

    cfg = get_config("olmo_1b").reduced().with_(vocab=512)
    root = str(tmp_path / "ds")
    make_token_dataset(root, n_docs=64, seq_len=64, vocab=512, shard_rows=64)
    losses = {}
    for mt in ("float32", "int8"):
        loop = TrainLoopConfig(
            steps=6, ckpt_every=100, ckpt_dir=str(tmp_path / mt), log_every=1000,
            adamw=optim.AdamWConfig(lr=1e-3, warmup_steps=2, moment_dtype=mt),
        )
        losses[mt] = train(build_model(cfg), DataLoader(RaDataset(root), 8, seed=1), loop,
                           resume=False)["losses"]
    np.testing.assert_allclose(losses["int8"], losses["float32"], rtol=2e-2)
