"""Mamba2 SSD chunk-scan kernel.

Per (batch, head) the chunked dual form is three MXU matmuls per chunk plus
an O(P·N) state update; the (P, N) state lives in VMEM scratch carried over
the sequential chunk grid dimension. Shapes per instance (Q = chunk):

    x     (Q, P)   input (already dt-scaled)
    dtA   (Q, 1)   per-step log decay (column vector for 2D iota friendliness)
    B, C  (Q, N)   input/output projections (n_groups=1: shared over heads)
    y     (Q, P)

    L     (Q, Q)   intra-chunk decay mask  exp(Acs_i - Acs_j) · (j<=i)
    y_diag = ((C Bᵀ) ⊙ L) x
    y_off  = (C ⊙ exp(Acs)) · state_in
    state  = state_in · exp(Acs_Q) + (B ⊙ decay)ᵀ x
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dta_ref, b_ref, c_ref, y_ref, state_ref, *, n_chunks, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)      # (Q, P)
    dta = dta_ref[0, 0].astype(jnp.float32)  # (Q, 1)
    Bm = b_ref[0, 0].astype(jnp.float32)     # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)     # (Q, N)

    acs = jnp.cumsum(dta[:, 0])[:, None]     # (Q, 1) inclusive cumsum
    # intra-chunk decay matrix
    diff = acs - acs.T                        # (Q, Q): Acs_i - Acs_j
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    )
    L = jnp.exp(jnp.where(tri, diff, -1e9))  # mask pre-exp (no inf)
    scores = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)  # (Q, Q)
    y = jnp.dot((scores * L).astype(x.dtype), x, preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the incoming state
    in_decay = jnp.exp(acs)                  # (Q, 1)
    state = state_ref[...]                    # (P, N)
    y += (jnp.dot(Cm, state.T, preferred_element_type=jnp.float32)) * in_decay

    # state update
    last = acs[chunk - 1, 0]
    decay_states = jnp.exp(last - acs)       # (Q, 1)
    state_ref[...] = state * jnp.exp(last) + jnp.dot(
        (x * decay_states).T, Bm, preferred_element_type=jnp.float32
    )
    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_scan_fwd(
    x: jax.Array,    # (B, H, L, P) dt-scaled inputs
    dtA: jax.Array,  # (B, H, L)
    Bm: jax.Array,   # (B, L, N)
    Cm: jax.Array,   # (B, L, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, L, P = x.shape
    N = Bm.shape[-1]
    assert L % chunk == 0, f"L={L} % chunk={chunk}"
    n_chunks = L // chunk
    dtA2 = dtA[..., None]  # (B, H, L, 1)
    Bm4 = Bm[:, None]      # (B, 1, L, N)
    Cm4 = Cm[:, None]

    kernel = functools.partial(_kernel, n_chunks=n_chunks, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ci: (b, 0, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ci: (b, 0, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dtA2, Bm4, Cm4)
