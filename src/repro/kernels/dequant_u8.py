"""Fused u8 -> float dequantize + normalize kernel (the image ingest path).

The paper's data plane delivers raw uint8 pixels by mmap; the first on-chip
op is dequantization + normalization ((x*scale + bias), e.g. scale=1/255).
Fusing them keeps the u8 bytes as the only HBM read (4x less traffic than
convert-then-normalize materializing f32 in between).

Grid: (row blocks, lane blocks) of a flattened (rows, C) view; tiles are
sized by :func:`block_shape` so they fit the VMEM budget on a TPU. A minor
axis that is not a multiple of 128 lanes is viewed as :func:`packed_width`
lanes where the element count allows.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM bytes one grid step may use: half of the 16 MiB that Mosaic allows a
# kernel by default on v5e, leaving the rest for its own scratch
VMEM_BUDGET = 8 << 20
_SUBLANES_U8 = 32  # uint8 tile height: (32, 128)
_LANES = 128


def packed_width(shape: Tuple[int, ...]) -> int:
    """Lanes of the packed ``(n / width, width)`` view in which the kernel
    reads a u8 block of ``shape``: ``lcm(C, 128)`` for a minor axis ``C``
    that is not a multiple of 128 when the element count ``n`` divides it,
    else 0 (the kernel reads ``(n / C, C)``). The channel parameters are
    tiled ``width / C`` times to match."""
    c = int(shape[-1])
    width = math.lcm(c, _LANES)
    n = math.prod(shape)
    return width if c % _LANES and n and n % width == 0 else 0


def block_shape(rows: int, cols: int, out_dtype) -> Tuple[int, int]:
    """(block_rows, block_cols) for a (rows, cols) u8 -> ``out_dtype`` pass.

    Per element a grid step holds the double-buffered u8 input, the
    double-buffered output and the int32 and float32 working values, all
    padded to whole (32, 128) tiles. Rows are a multiple of 32 or all of
    them; the lane axis stays whole unless one 32-row stripe of it is
    already over ``VMEM_BUDGET``, and is then tiled in multiples of 128."""
    per_elem = 2 + 2 * np.dtype(out_dtype).itemsize + 8
    lanes = -(-max(cols, 1) // _LANES) * _LANES
    stripe = _SUBLANES_U8 * per_elem
    if stripe * lanes > VMEM_BUDGET:
        block_cols = max(_LANES, VMEM_BUDGET // stripe // _LANES * _LANES)
        return min(rows, _SUBLANES_U8), min(cols, block_cols)
    block_rows = VMEM_BUDGET // (lanes * per_elem) // _SUBLANES_U8 * _SUBLANES_U8
    return (rows if block_rows >= rows else block_rows), cols


def _kernel(x_ref, scale_ref, bias_ref, o_ref):
    # Mosaic has no direct uint8 -> float32 cast; int32 holds every code exactly
    x = x_ref[...].astype(jnp.int32).astype(jnp.float32)
    o_ref[...] = (x * scale_ref[...] + bias_ref[...]).astype(o_ref.dtype)


def dequant_u8_fwd(
    x: jax.Array,      # (rows, C) uint8
    scale: jax.Array,  # (C,) f32 — per-channel scale
    bias: jax.Array,   # (C,) f32
    *,
    out_dtype=jnp.float32,
    block: Tuple[int, int],
    interpret: bool = False,
) -> jax.Array:
    rows, C = x.shape
    br, bc = block
    return pl.pallas_call(
        _kernel,
        grid=(pl.cdiv(rows, br), pl.cdiv(C, bc)),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, C), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(x, scale.astype(jnp.float32)[None, :], bias.astype(jnp.float32)[None, :])
