"""Flash-attention forward kernel (causal / sliding-window, GQA-aware).

Grid: (batch, q_heads, n_q_blocks, n_kv_blocks); the KV-block dimension is
minor-most ("arbitrary" semantics ⇒ sequential on TPU), so the online-
softmax state (m, l, acc) lives in VMEM scratch carried across KV blocks.
Block shapes: q (Bq, hd), k/v (Bk, hd) — hd padded to a multiple of 128 by
the wrapper, Bq/Bk default 128 ⇒ MXU-shaped (128, hd)x(hd, 128) matmuls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, causal, window, block_q, block_k, n_kv_blocks, seq_q, seq_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (Bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (Bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)  # (Bk, hd)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (Bq, Bk)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    ok = kpos < seq_k
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]  # (Bq, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_cur

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (B, H, Sq, hd)
    k: jax.Array,  # (B, KV, Sk, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    group = H // KV
    scale = scale if scale is not None else hd**-0.5
    n_q = pl.cdiv(Sq, block_q)
    n_k = pl.cdiv(Sk, block_k)

    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        n_kv_blocks=n_k,
        seq_q=Sq,
        seq_k=Sk,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
