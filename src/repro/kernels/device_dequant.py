"""A quantized u8 block's trip to one device (DESIGN.md §12): put it in the
view the ``dequant_u8`` kernel reads, decode it there, and hand back the
float array in the block's logical shape.

The device feed (``repro.data.device_loader``) and the cold-start restore
(``repro.checkpoint.coldstart``) both go through :class:`DeviceDequant`. A
block whose minor axis is not a multiple of 128 lanes crosses the link in
the kernel's packed view (``dequant_u8.packed_width``), a free reshape of
the C-contiguous host block, and only the float result is reshaped back,
after the kernel. Any other block crosses in its own shape, and
``ops.dequant_u8`` flattens it inside its jit. The kernel is looked up as
the attribute ``ops.dequant_u8`` on every call, so a test can stand in for
it.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import obs
from . import ops
from .dequant_u8 import packed_width

# quantized blocks put in the packed view
_PACKED = obs.counter("device_loader.packed")


class DeviceDequant:
    """The decode of one quantized field or leaf: ``quant``'s channel
    parameters for blocks of logical ``shape`` (a leading -1 where the
    number of rows varies), put on each device once, tiled to the packed
    width for a packed block."""

    def __init__(self, quant, shape: Tuple[int, ...], *, interpret: Optional[bool] = None):
        self.shape = tuple(int(d) for d in shape)
        self.out_dtype = np.dtype(quant.orig_dtype)
        self._host = quant.channel_params(self.shape[-1])
        self._on: Dict[Tuple[Any, int], Tuple[Any, Any]] = {}
        self._interpret = interpret

    def put(self, block: np.ndarray, device: Any) -> jax.Array:
        """``jax.device_put`` u8 host ``block`` onto ``device`` (None: jax's
        default, uncommitted) in the view the kernel reads; does not wait
        for the transfer."""
        width = packed_width(block.shape)
        if width:
            _PACKED.add(1)
            block = block.reshape(-1, width)
        return jax.device_put(block, device)

    def decode(self, x: jax.Array, device: Any) -> jax.Array:
        """Dispatch the kernel on ``x``, a block :meth:`put` on ``device``,
        and return the float result in the logical shape."""
        width = int(x.shape[-1])
        scale, bias = self._params_on(device, width)
        out = ops.dequant_u8(x, scale, bias, out_dtype=self.out_dtype,
                             interpret=self._interpret)
        return out if width == self.shape[-1] else out.reshape(self.shape)

    def to_device(self, block: np.ndarray, device: Any) -> jax.Array:
        """:meth:`put` then :meth:`decode`, without waiting."""
        return self.decode(self.put(block, device), device)

    def _params_on(self, device: Any, width: int) -> Tuple[Any, Any]:
        p = self._on.get((device, width))
        if p is None:
            reps = width // self.shape[-1]
            tiled = tuple(np.tile(a, reps) for a in self._host)
            p = self._on[(device, width)] = jax.device_put(tiled, device)
        return p


def compile_decode(shape: Tuple[int, ...], out_dtype: Any, *,
                   interpret: Optional[bool] = None) -> None:
    """AOT lower and compile the kernel call :meth:`DeviceDequant.decode`
    makes for a block of logical ``shape``, without running it."""
    width = packed_width(shape)
    wire = (math.prod(shape) // width, width) if width else tuple(shape)
    c = wire[-1]
    ops.dequant_u8.lower(
        jax.ShapeDtypeStruct(wire, jnp.uint8),
        jax.ShapeDtypeStruct((c,), jnp.float32),
        jax.ShapeDtypeStruct((c,), jnp.float32),
        out_dtype=jnp.dtype(out_dtype), interpret=interpret,
    ).compile()
