"""jit'd public wrappers for the Pallas kernels.

``interpret=None`` (default) auto-selects: real Mosaic lowering on TPU,
interpret mode elsewhere (this container is CPU-only — TPU is the target,
interpret mode is the validation vehicle).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_fwd
from .dequant_u8 import block_shape, dequant_u8_fwd
from .flash_attention import flash_attention_fwd
from .ssd_scan import ssd_scan_fwd


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(
    q, k, v, *, causal: bool = True, window: int = 0,
    block_q: int = 128, block_k: int = 128, interpret: Optional[bool] = None,
):
    """q (B,H,Sq,hd), k/v (B,KV,Sk,hd) -> (B,H,Sq,hd). GQA via KV broadcast."""
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window,
        block_q=min(block_q, q.shape[2]), block_k=min(block_k, k.shape[2]),
        interpret=_auto_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("window", "block_s", "interpret"))
def decode_attention(
    q, k, v, pos, *, window: int = 0, block_s: int = 512, interpret: Optional[bool] = None
):
    """q (B,H,hd) with H = KV*group, k/v (B,KV,S,hd) -> (B,H,hd)."""
    B, H, hd = q.shape
    KV = k.shape[1]
    g = H // KV
    out = decode_attention_fwd(
        q.reshape(B, KV, g, hd), k, v, pos,
        window=window, block_s=min(block_s, k.shape[2]),
        interpret=_auto_interpret(interpret),
    )
    return out.reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dtA, Bm, Cm, *, chunk: int = 128, interpret: Optional[bool] = None):
    """x (B,H,L,P), dtA (B,H,L), Bm/Cm (B,L,N) -> y (B,H,L,P)."""
    return ssd_scan_fwd(
        x, dtA, Bm, Cm, chunk=min(chunk, x.shape[2]), interpret=_auto_interpret(interpret)
    )


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def dequant_u8(x, scale, bias, *, out_dtype=jnp.float32, interpret: Optional[bool] = None):
    """x (..., C) uint8 -> (..., C) float, fused (x*scale + bias).

    Tiles come from ``dequant_u8.block_shape``, so every caller of one
    shape (device feed, cold-start restore, its warmup) compiles the same
    kernel. A narrow last axis (C not a multiple of 128, e.g. RGB pixels)
    is repacked to lcm(C, 128) lanes with the channel params tiled to
    match, when the element count allows it; the result is unchanged."""
    shape = x.shape
    if x.size == 0:
        return jnp.zeros(shape, out_dtype)
    C = shape[-1]
    width = math.lcm(C, 128)
    if C % 128 and x.size % width == 0:
        reps = width // C
        x2, scale, bias = x.reshape(-1, width), jnp.tile(scale, reps), jnp.tile(bias, reps)
    else:
        x2 = x.reshape(-1, C)
    block = block_shape(x2.shape[0], x2.shape[1], out_dtype)
    out = dequant_u8_fwd(
        x2, scale, bias, out_dtype=out_dtype, block=block,
        interpret=_auto_interpret(interpret),
    )
    return out.reshape(shape)
