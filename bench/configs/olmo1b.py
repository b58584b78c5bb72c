"""Plain reference for ``olmo1b.json``: OLMo-1B's weights from a seed.

It imports nothing of the program under test. Each leaf is a float32
normal draw from ``fold_in(key, crc32(path))``, scaled, and cast to the
parameter dtype; stacked layers draw from ``split(key, n_layers)`` (the
``init`` the configuration states). Leaves are named by their dotted
paths in the program's parameter tree.
"""

from __future__ import annotations

import math
import zlib
from typing import Dict

import jax
import jax.numpy as jnp


# ------------------------------------------------------------------ weights
def leaf_specs(cfg: dict) -> Dict[str, tuple]:
    """Leaf name -> (shape, init path, std). Stacked leaves lead with the
    layer axis."""
    m = cfg["model"]
    d, h, ff, v, n = m["d_model"], m["n_heads"], m["d_ff"], m["vocab"], m["n_layers"]
    hd = d // h
    # the stated init scales each matrix by 1/sqrt(shape[-2]) ("fan-in" of
    # the trailing two axes), the embedding by 1/sqrt(d_model)
    return {
        "embed": ((v, d), "embed", 1.0 / math.sqrt(d)),
        "dense_layers.attn.wq": ((n, d, h, hd), "attn.wq", 1.0 / math.sqrt(h)),
        "dense_layers.attn.wk": ((n, d, h, hd), "attn.wk", 1.0 / math.sqrt(h)),
        "dense_layers.attn.wv": ((n, d, h, hd), "attn.wv", 1.0 / math.sqrt(h)),
        "dense_layers.attn.wo": ((n, h, hd, d), "attn.wo", 1.0 / math.sqrt(hd)),
        "dense_layers.ffn.w_up": ((n, d, ff), "mlp.w_up", 1.0 / math.sqrt(d)),
        "dense_layers.ffn.w_gate": ((n, d, ff), "mlp.w_gate", 1.0 / math.sqrt(d)),
        "dense_layers.ffn.w_down": ((n, ff, d), "mlp.w_down", 1.0 / math.sqrt(ff)),
    }


def base_seed(seed: int) -> int:
    """The run's seed as the init takes it: ``seed mod 2**31``."""
    return seed % (1 << 31)


def base_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(base_seed(seed))


def _draw(key, path: str, shape, std: float, dtype):
    k = jax.random.fold_in(key, jnp.uint32(zlib.crc32(path.encode()) % (1 << 31)))
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def init_leaf(cfg: dict, seed: int, name: str) -> jax.Array:
    """One leaf of the initial weights (call under ``jax.jit``)."""
    shape, path, std = leaf_specs(cfg)[name]
    dtype = jnp.dtype(cfg["model"]["param_dtype"])
    keys = jax.random.split(base_key(seed), 8)
    if name == "embed":
        return _draw(keys[0], path, shape, std, dtype)
    layer_keys = jax.random.split(keys[1], shape[0])
    return jax.vmap(lambda k: _draw(k, path, shape[1:], std, dtype))(layer_keys)


def init(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Every leaf, by name (call under ``jax.jit``)."""
    return {name: init_leaf(cfg, seed, name) for name in leaf_specs(cfg)}
