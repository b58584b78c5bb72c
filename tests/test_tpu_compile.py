"""The dequant kernel compiled for a TPU v5e that is described, not attached.

Interpret mode cannot see what Mosaic refuses (casts it has no lowering
for, tiles over the VMEM limit), so each case compiles ``ops.dequant_u8``
with ``interpret=False`` for one chip of a ``v5e:2x2`` topology, at the
widths the device feed and the cold-start restore hand it.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.dequant_u8 import VMEM_BUDGET, block_shape, packed_width


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the persistent
    # cache; keep it off so these compiles neither write nor warn
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


# (leaf shape, logical dtype): OLMo-1B's tied embedding and MLP matrices
# as bf16 checkpoint leaves, a CIFAR-10-shaped batch of 256 RGB images
# (last axis 3) from the device feed, and one small leaf
CASES = {
    "olmo1b-embed": ((50304, 2048), jnp.bfloat16),
    "olmo1b-mlp-in": ((2048, 8192), jnp.bfloat16),
    "olmo1b-mlp-out": ((8192, 2048), jnp.bfloat16),
    "cifar-batch": ((256, 32, 32, 3), jnp.float32),
    "small": ((10, 8), jnp.float32),
}


def _kernel_view(shape):
    """The (rows, lanes) view ``ops.dequant_u8`` hands the kernel."""
    width = packed_width(shape) or shape[-1]
    return int(np.prod(shape)) // width, width


@pytest.mark.parametrize("name", sorted(CASES))
def test_dequant_compiles_for_v5e(one_chip, name):
    shape, out_dtype = CASES[name]
    rows, cols = _kernel_view(shape)
    br, bc = block_shape(rows, cols, out_dtype)
    assert br == rows or br % 32 == 0
    assert bc == cols or bc % 128 == 0
    per_elem = 2 + 2 * jnp.dtype(out_dtype).itemsize + 8
    assert max(br, 32) * -(-bc // 128) * 128 * per_elem <= VMEM_BUDGET

    c = shape[-1]
    compiled = ops.dequant_u8.lower(
        jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip),
        out_dtype=out_dtype, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
