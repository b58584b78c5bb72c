"""GQA decode attention kernel (flash-decoding style).

One query token per sequence attends a long KV cache — purely memory-bound
on TPU (roofline: cache bytes / HBM bw). Grid: (batch, kv_heads,
n_s_blocks); the S-block dimension is sequential, with online-softmax state
(m, l, acc) for the whole q-head *group* in VMEM scratch. Masking uses the
scalar-prefetched current position so cache slots beyond ``pos`` are dead.

q is reshaped to (B, KV, group, hd) by the wrapper; output (B, KV, group, hd).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, block_s, n_s_blocks, window):
    si = pl.program_id(2)
    pos = pos_ref[0]

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (g, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (Bs, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (g, Bs)
    kpos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = kpos <= pos
    if window > 0:
        ok &= kpos > pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(si == n_s_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_fwd(
    q: jax.Array,    # (B, KV, group, hd)
    k: jax.Array,    # (B, KV, S, hd)
    v: jax.Array,
    pos: jax.Array,  # scalar int32: positions <= pos are live
    *,
    window: int = 0,
    scale: float | None = None,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, KV, g, hd = q.shape
    S = k.shape[2]
    scale = scale if scale is not None else hd**-0.5
    n_s = pl.cdiv(S, block_s)

    kernel = functools.partial(
        _kernel, scale=scale, block_s=block_s, n_s_blocks=n_s, window=window
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b, h, si, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, hd), lambda b, h, si, *_: (b, h, si, 0)),
            pl.BlockSpec((1, 1, block_s, hd), lambda b, h, si, *_: (b, h, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda b, h, si, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), q, k, v)
