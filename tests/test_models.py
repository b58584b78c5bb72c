"""Per-architecture smoke tests (reduced configs): one forward/train step on
CPU asserting output shapes + finite values, plus serving-path consistency.
The FULL configs are exercised only via the dry-run (no allocation)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import all_arch_ids, get_config
from repro.models import build_model

ARCHS = all_arch_ids(include_paper=True)


def _batch(cfg, B=2, S=32, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    b = {"tokens": jnp.asarray(rng.integers(1, cfg.vocab, (B, S)), jnp.int32)}
    if cfg.family == "vlm":
        b["patch_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_patches, cfg.d_model)) * 0.1, jnp.float32
        )
    if cfg.family == "encdec":
        b["frames"] = jnp.asarray(rng.normal(size=(B, 64, cfg.d_model)) * 0.1, jnp.float32)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)

    def loss_fn(p):
        return model.train_loss(p, batch)

    (loss, metrics), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params)
    assert np.isfinite(float(loss)), arch
    assert float(loss) > 0
    # every grad leaf finite and at least one nonzero
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(l, np.float32))) for l in leaves), arch
    assert any(float(jnp.max(jnp.abs(l.astype(jnp.float32)))) > 0 for l in leaves), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_param_count_sane(arch):
    """Analytic parameter count of the FULL config lands near its nameplate."""
    cfg = get_config(arch)
    n = cfg.param_count()
    nameplate = {
        "gemma3-12b": 12e9, "olmo-1b": 1.2e9, "internlm2-1.8b": 1.9e9,
        "qwen2.5-14b": 14e9, "llava-next-mistral-7b": 7.1e9,
        "deepseek-v3-671b": 671e9, "kimi-k2-1t-a32b": 1.0e12,
        "whisper-medium": 0.76e9, "mamba2-780m": 0.78e9, "zamba2-1.2b": 1.2e9,
        "paper_lm": 6e6,
    }[cfg.name]
    assert 0.5 * nameplate < n < 1.7 * nameplate, (arch, n, nameplate)


@pytest.mark.parametrize("arch", ["qwen2_5_14b", "gemma3_12b", "mamba2_780m", "zamba2_1_2b"])
def test_prefill_decode_consistency(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    B, S = 2, 16
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (B, S)), jnp.int32)
    logits_pf, _ = jax.jit(model.prefill)(params, {"tokens": tokens})
    cache = model.empty_cache(B, S + 4)
    step = jax.jit(model.decode_step)
    for t in range(S):
        logits_dec, cache = step(params, cache, tokens[:, t : t + 1])
    np.testing.assert_allclose(
        np.asarray(logits_pf), np.asarray(logits_dec), rtol=2e-3, atol=2e-4
    )


def test_gemma3_local_global_pattern():
    cfg = get_config("gemma3_12b")
    model = build_model(cfg)
    g, th = model._layer_flags(cfg.n_layers)
    g = np.asarray(g)
    assert g.sum() == cfg.n_layers // 6            # 1 global in 6
    assert g[5] == 1 and g[0] == 0 and g[11] == 1  # positions 6, 12, ...
    th = np.asarray(th)
    assert th[5] == 1_000_000.0 and th[0] == 10_000.0


def test_vlm_prefix_masking():
    """Loss must only cover text positions (patches are prefix)."""
    cfg = get_config("llava_next_mistral_7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = _batch(cfg)
    loss1, _ = jax.jit(model.train_loss)(params, b)
    # change ONLY the patch embeddings: loss must change (prefix feeds in)
    b2 = dict(b)
    b2["patch_embeds"] = b["patch_embeds"] * 2.0
    loss2, _ = jax.jit(model.train_loss)(params, b2)
    assert not np.isclose(float(loss1), float(loss2))


def test_mtp_loss_included():
    cfg = get_config("deepseek_v3_671b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = _batch(cfg)
    loss, metrics = jax.jit(model.train_loss)(params, b)
    assert "mtp" in metrics
    assert np.isfinite(float(metrics["mtp"]))
    np.testing.assert_allclose(
        float(loss),
        float(metrics["ce"] + metrics["aux"] + cfg.mtp_weight * metrics["mtp"]),
        rtol=1e-5,
    )


def test_sliding_window_shrinks_context():
    """A token far outside the window must not influence the last logits."""
    cfg = get_config("llava_next_mistral_7b").reduced().with_(
        family="dense", n_patches=0, sliding_window=8
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (1, 32)), jnp.int32)
    tokens2 = tokens.at[0, 0].set((int(tokens[0, 0]) + 1) % cfg.vocab)
    l1, _ = jax.jit(model.prefill)(params, {"tokens": tokens})
    l2, _ = jax.jit(model.prefill)(params, {"tokens": tokens2})
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)


def test_ssd_chunk_invariance():
    """Mamba2 output must not depend on the chunk size (algebraic identity)."""
    import dataclasses

    cfg = get_config("mamba2_780m").reduced()
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (2, 64)), jnp.int32)
    outs = []
    for chunk in (16, 32, 64):
        c = cfg.with_(ssm=dataclasses.replace(cfg.ssm, chunk=chunk))
        model = build_model(c)
        params = model.init(jax.random.PRNGKey(0))
        l, _ = jax.jit(model.prefill)(params, {"tokens": tokens})
        outs.append(np.asarray(l))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(outs[0], outs[2], rtol=1e-4, atol=1e-5)


def test_init_is_the_same_in_every_process():
    """Weights made from a seed must not depend on the process's str-hash
    salt (PYTHONHASHSEED): a checkpoint, a reference and a restarted job
    all rebuild the same model."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    code = (
        "import jax, numpy as np\n"
        "from repro.configs import get_config\n"
        "from repro.models import build_model\n"
        "p = build_model(get_config('olmo_1b').reduced()).init(jax.random.PRNGKey(0))\n"
        "print(sum(float(np.abs(np.asarray(x, np.float64)).sum()) for x in jax.tree_util.tree_leaves(p)))\n"
    )
    sums = {
        subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True, timeout=120, check=True,
            env=dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu", PYTHONHASHSEED=str(salt)),
        ).stdout.split()[-1]
        for salt in (1, 2)
    }
    assert len(sums) == 1, sums
