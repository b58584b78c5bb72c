"""Sharded AdamW with memory-plan options for trillion-parameter configs.

Moments are stored per the model config's ``opt_moment_dtype``:

* ``float32`` — standard AdamW (dense archs).
* ``int8``    — blockwise-quantized moments (block 128 along the trailing
  axis, absmax scaling), the 8-bit-Adam trick that brings deepseek-v3 /
  kimi-k2 optimizer state under the 16 GiB/chip HBM budget (DESIGN.md §3).
  The second moment rounds UP: rounded to nearest, its small entries in a
  block decode as 0, and their update divides by ``eps`` alone.

Optimizer state shards exactly like its parameter (same tree structure),
so partition specs map 1:1.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

BLOCK = 128


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    moment_dtype: str = "float32"  # 'float32' | 'int8'
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


# ------------------------------------------------------------- quantization
def _pad_to_block(x: jax.Array) -> Tuple[jax.Array, int]:
    n = x.shape[-1]
    pad = (-n) % BLOCK
    if pad:
        x = jnp.concatenate([x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)
    return x, n


def quantize_blockwise(x: jax.Array, round_up: bool = False) -> Dict[str, jax.Array]:
    """int8 absmax quantization over trailing-axis blocks of 128;
    ``round_up`` never decodes below ``x`` (for non-negative ``x``)."""
    xp, _ = _pad_to_block(x.astype(jnp.float32))
    blocks = xp.reshape(*xp.shape[:-1], -1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    rounded = jnp.ceil(blocks / scale) if round_up else jnp.round(blocks / scale)
    q = jnp.clip(rounded, -127, 127).astype(jnp.int8)
    return {"q": q.reshape(xp.shape), "scale": scale[..., 0]}


def dequantize_blockwise(d: Dict[str, jax.Array], n: int) -> jax.Array:
    q = d["q"].astype(jnp.float32)
    blocks = q.reshape(*q.shape[:-1], -1, BLOCK)
    x = blocks * d["scale"][..., None]
    x = x.reshape(q.shape)
    return x[..., :n]


# ------------------------------------------------------------------- state
def _quantizable(p) -> bool:
    """Blockwise int8 pays off only for real tensors (scalars/tiny vectors
    keep fp32 moments — they're negligible memory anyway)."""
    return p.ndim >= 1 and p.size >= BLOCK


def init_state(params: Any, cfg: AdamWConfig) -> Dict[str, Any]:
    def zero_moment(p):
        z = jnp.zeros(p.shape, jnp.float32)
        if cfg.moment_dtype == "int8" and _quantizable(p):
            return quantize_blockwise(z)
        return z

    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree_util.tree_map(zero_moment, params),
        "v": jax.tree_util.tree_map(zero_moment, params),
    }


def _lr_at(step: jax.Array, cfg: AdamWConfig) -> jax.Array:
    """Linear warmup + cosine decay to min_lr_frac."""
    s = step.astype(jnp.float32)
    warm = s / jnp.maximum(1.0, cfg.warmup_steps)
    t = jnp.clip(
        (s - cfg.warmup_steps) / jnp.maximum(1.0, cfg.total_steps - cfg.warmup_steps), 0.0, 1.0
    )
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return cfg.lr * jnp.where(s < cfg.warmup_steps, warm, cos)


def global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def apply_updates(
    params: Any, grads: Any, state: Dict[str, Any], cfg: AdamWConfig
) -> Tuple[Any, Dict[str, Any], Dict[str, jax.Array]]:
    step = state["step"] + 1
    lr = _lr_at(step, cfg)
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9)) if cfg.grad_clip else 1.0

    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)
    quantized = cfg.moment_dtype == "int8"

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * clip
        q_leaf = quantized and isinstance(m, dict)
        n = p.shape[-1] if p.ndim else 1
        m_f = dequantize_blockwise(m, n) if q_leaf else m
        v_f = dequantize_blockwise(v, n) if q_leaf else v
        m_f = cfg.b1 * m_f + (1 - cfg.b1) * g
        v_f = cfg.b2 * v_f + (1 - cfg.b2) * g * g
        u = (m_f / b1c) / (jnp.sqrt(v_f / b2c) + cfg.eps)
        if cfg.weight_decay and p.ndim >= 2:  # decay matrices only
            u = u + cfg.weight_decay * p.astype(jnp.float32)
        p2 = (p.astype(jnp.float32) - lr * u).astype(p.dtype)
        m2 = quantize_blockwise(m_f) if q_leaf else m_f
        v2 = quantize_blockwise(v_f, round_up=True) if q_leaf else v_f
        return p2, m2, v2

    def upd_maybe_scanned(p, g, m, v):
        # layer-stacked leaves (leading scan dim): update one layer at a time
        # so the f32 moment/update temporaries are layer-sized, not
        # stack-sized (a (58, 16, 7168, 2048) f32 temp is 50 GiB/device;
        # scanned it is 0.9 GiB — see EXPERIMENTS.md §Perf deepseek log).
        stacked = p.ndim >= 3 and p.shape[0] <= 128 and (p.size // p.shape[0]) >= (1 << 20)
        if not stacked:
            return upd(p, g, m, v)

        def body(_, slices):
            ps, gs, ms, vs = slices
            return None, upd(ps, gs, ms, vs)

        _, (p2, m2, v2) = jax.lax.scan(body, None, (p, g, m, v))
        return p2, m2, v2

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    # quantized moments are dicts (deeper trees); flatten_up_to stops at the
    # param treedef so each entry is the whole {"q","scale"} dict.
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd_maybe_scanned(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    new_state = {"step": step, "m": new_m, "v": new_v}
    return new_p, new_state, {"lr": lr, "grad_norm": gnorm}
