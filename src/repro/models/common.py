"""Shared building blocks: init helpers, norms, RoPE, embeddings, losses.

Parameters are plain nested dicts of jnp arrays (pytrees), so the whole
model state is transparently compatible with `jax.eval_shape` (abstract
dry-run init), `jax.tree_util` mapping for partition specs, and the
RawArray checkpoint store (one leaf = one .ra file).
"""

from __future__ import annotations

import math
import zlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


# --------------------------------------------------------------------- init
class Initializer:
    """Deterministic per-path param init: fold the path string into the key
    so layer stacking (vmap over leading axis) stays reproducible."""

    def __init__(self, key: jax.Array, dtype):
        self.key = key
        self.dtype = dtype

    def _fold(self, path: str) -> jax.Array:
        # crc32, not hash(): str hashes are salted per process
        h = jnp.uint32(zlib.crc32(path.encode()) % (2**31))
        return jax.random.fold_in(self.key, h)

    def normal(self, path: str, shape, scale: float = 0.02) -> jax.Array:
        return (
            jax.random.normal(self._fold(path), shape, dtype=jnp.float32) * scale
        ).astype(self.dtype)

    def fanin(self, path: str, shape) -> jax.Array:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        return self.normal(path, shape, scale=1.0 / math.sqrt(fan_in))

    def zeros(self, path: str, shape) -> jax.Array:
        return jnp.zeros(shape, dtype=self.dtype)

    def ones(self, path: str, shape) -> jax.Array:
        return jnp.ones(shape, dtype=self.dtype)

    def value(self, path: str, val) -> jax.Array:
        return jnp.asarray(val, dtype=self.dtype)


def stack_init(n: int, init_fn: Callable[[Initializer], Params], key, dtype) -> Params:
    """Initialize ``n`` layers and stack each leaf on a leading axis, for
    ``lax.scan`` over layers."""
    def one(k):
        return init_fn(Initializer(k, dtype))
    keys = jax.random.split(key, n)
    return jax.vmap(one)(keys)


# --------------------------------------------------------------------- norms
def rmsnorm(x: jax.Array, weight: Optional[jax.Array], eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    if weight is not None:
        y = y * (1.0 + weight.astype(jnp.float32))
    return y.astype(dt)


def layernorm(
    x: jax.Array,
    weight: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    eps: float = 1e-5,
) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def make_norm(kind: str):
    """Return (init_fn(ini, path, d) -> params|None, apply_fn(params, x))."""
    if kind == "rmsnorm":
        return (
            lambda ini, path, d: {"scale": ini.zeros(path + ".scale", (d,))},
            lambda p, x: rmsnorm(x, p["scale"]),
        )
    if kind == "layernorm":
        return (
            lambda ini, path, d: {
                "scale": ini.ones(path + ".scale", (d,)),
                "bias": ini.zeros(path + ".bias", (d,)),
            },
            lambda p, x: layernorm(x, p["scale"], p["bias"]),
        )
    if kind == "layernorm_np":  # olmo: non-parametric
        return (lambda ini, path, d: {}, lambda p, x: layernorm(x))
    raise ValueError(f"unknown norm {kind}")


# --------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, head_dim), positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved_theta(
    x: jax.Array, positions: jax.Array, theta_a: float, theta_b: float, use_b
) -> jax.Array:
    """Select between two RoPE bases per-layer inside a scan (gemma3)."""
    a = apply_rope(x, positions, theta_a)
    b = apply_rope(x, positions, theta_b)
    return jnp.where(use_b, b, a)


# --------------------------------------------------------------------- misc
def activation(kind: str):
    if kind == "silu":
        return jax.nn.silu
    if kind == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {kind}")


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap > 0 else x


def cross_entropy_loss(
    logits: jax.Array,  # (B, S, V) possibly sharded on V
    labels: jax.Array,  # (B, S) int32
    mask: Optional[jax.Array] = None,  # (B, S) 1.0 = count
    z_loss: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Numerically stable CE written as explicit max/sum reductions so GSPMD
    inserts all-reduces when the vocab dim is model-sharded (full logits are
    never gathered)."""
    logits32 = logits.astype(jnp.float32)
    m = jnp.max(logits32, axis=-1, keepdims=True)
    shifted = logits32 - jax.lax.stop_gradient(m)
    sumexp = jnp.sum(jnp.exp(shifted), axis=-1)
    lse = jnp.log(sumexp) + m[..., 0]
    label_logit = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
    nll = lse - label_logit
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    total = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll * mask) / total
    acc = jnp.sum((jnp.argmax(logits32, axis=-1) == labels) * mask) / total
    return loss, acc


def embed_lookup(table: jax.Array, ids: jax.Array, scale: bool, cdtype) -> jax.Array:
    x = jnp.take(table, ids, axis=0).astype(cdtype)
    if scale:
        x = x * jnp.asarray(math.sqrt(table.shape[1]), dtype=cdtype)
    return x
