"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention, dequant_u8, flash_attention, ssd_scan
from repro.kernels import ref

_rng = np.random.default_rng(0)


def _arr(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(_rng.normal(size=shape) * scale, dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 2, 2, 128, 64),
    (2, 4, 2, 256, 64),
    (1, 8, 2, 384, 128),   # S not a multiple of block_k=128? 384 = 3x128 ok
    (2, 2, 1, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(B, H, KV, S, hd, dtype, causal, window):
    q, k, v = _arr(B, H, S, hd, dtype=dtype), _arr(B, KV, S, hd, dtype=dtype), _arr(B, KV, S, hd, dtype=dtype)
    out = flash_attention(q, k, v, causal=causal, window=window)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


@pytest.mark.parametrize("B,KV,g,S,hd,pos", [
    (1, 2, 4, 256, 64, 100),
    (2, 1, 8, 512, 128, 511),
    (2, 4, 1, 128, 64, 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, KV, g, S, hd, pos, dtype):
    q = _arr(B, KV * g, hd, dtype=dtype)
    k, v = _arr(B, KV, S, hd, dtype=dtype), _arr(B, KV, S, hd, dtype=dtype)
    out = decode_attention(q, k, v, pos)
    want = ref.decode_attention_ref(q.reshape(B, KV, g, hd), k, v, pos).reshape(B, KV * g, hd)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


def test_decode_attention_masks_beyond_pos():
    """Cache rows beyond pos must be completely dead."""
    B, KV, g, S, hd = 1, 1, 2, 128, 64
    q = _arr(B, KV * g, hd)
    k, v = _arr(B, KV, S, hd), _arr(B, KV, S, hd)
    out1 = decode_attention(q, k, v, 10)
    k2 = k.at[:, :, 11:].set(999.0)
    v2 = v.at[:, :, 11:].set(-999.0)
    out2 = decode_attention(q, k2, v2, 10)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


@pytest.mark.parametrize("B,H,L,P,N,chunk", [
    (1, 2, 128, 32, 16, 32),
    (2, 3, 256, 64, 32, 64),
    (1, 1, 64, 16, 8, 64),   # single chunk
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_scan_sweep(B, H, L, P, N, chunk, dtype):
    x = _arr(B, H, L, P, dtype=dtype, scale=0.5)
    dtA = -jnp.abs(_arr(B, H, L, dtype=dtype, scale=0.3))
    Bm, Cm = _arr(B, L, N, dtype=dtype, scale=0.5), _arr(B, L, N, dtype=dtype, scale=0.5)
    out = ssd_scan(x, dtA, Bm, Cm, chunk=chunk)
    want = ref.ssd_scan_ref(x, dtA, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-3, atol=1e-4)


def test_ssd_kernel_matches_model_ssd():
    """Kernel must agree with the model-side pure-JAX chunked SSD too."""
    from repro.models.mamba import ssd_chunked

    B, H, L, P, N = 2, 2, 128, 16, 8
    x = _arr(B, L, H, P, scale=0.4)           # model layout (B, L, H, P)
    dtA = -jnp.abs(_arr(B, L, H, scale=0.2))
    Bm, Cm = _arr(B, L, N, scale=0.5), _arr(B, L, N, scale=0.5)
    y_model, _ = ssd_chunked(x, dtA, Bm, Cm, chunk=32)
    y_kernel = ssd_scan(
        jnp.moveaxis(x, 2, 1), jnp.moveaxis(dtA, 2, 1), Bm, Cm, chunk=32
    )
    np.testing.assert_allclose(
        np.asarray(jnp.moveaxis(y_kernel, 1, 2)), np.asarray(y_model), rtol=1e-3, atol=1e-4
    )


@pytest.mark.parametrize("rows,C", [(10, 8), (300, 24), (257, 128)])
@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_dequant_sweep(rows, C, out_dtype):
    x = jnp.asarray(_rng.integers(0, 256, (rows, C)), jnp.uint8)
    scale, bias = _arr(C, scale=0.01), _arr(C)
    out = dequant_u8(x, scale, bias, out_dtype=out_dtype)
    want = ref.dequant_u8_ref(x, scale, bias, out_dtype)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("rows,cols,out_dtype", [
    (50304, 2048, jnp.bfloat16),   # more rows than one tile takes
    (20, 2048, jnp.float32),       # fewer rows than one u8 tile: all of them
    (4096, 384, jnp.float32),      # repacked RGB pixels
    (1, 1 << 22, jnp.float32),     # one row over the budget: lanes tiled
    (64, 1 << 20, jnp.bfloat16),
])
def test_dequant_block_shape_rules(rows, cols, out_dtype):
    from repro.kernels.dequant_u8 import VMEM_BUDGET, block_shape

    br, bc = block_shape(rows, cols, out_dtype)
    assert br == rows or br % 32 == 0
    assert bc == cols or bc % 128 == 0
    per_elem = 2 + 2 * jnp.dtype(out_dtype).itemsize + 8
    assert max(br, 32) * -(-bc // 128) * 128 * per_elem <= VMEM_BUDGET
    if bc < cols:  # lanes are tiled only when one 32-row stripe cannot fit
        assert 32 * -(-cols // 128) * 128 * per_elem > VMEM_BUDGET


@pytest.mark.parametrize("rows,cols", [(70, 1000), (3, 4096)])
def test_dequant_tiled_lanes_match_ref(rows, cols):
    """Lane-tiled blocks, with partial edge blocks on both axes, still give
    the reference's values."""
    from repro.kernels.dequant_u8 import dequant_u8_fwd

    block = (min(rows, 32), 256)
    x = jnp.asarray(_rng.integers(0, 256, (rows, cols)), jnp.uint8)
    scale, bias = _arr(cols, scale=0.01), _arr(cols)
    out = dequant_u8_fwd(x, scale, bias, block=block, interpret=True)
    want = ref.dequant_u8_ref(x, scale, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_dequant_narrow_channels_repacked():
    """(…, 3) pixels go through 384-lane rows with tiled channel params."""
    x = jnp.asarray(_rng.integers(0, 256, (4, 32, 32, 3)), jnp.uint8)
    scale, bias = _arr(3, scale=0.01), _arr(3)
    out = dequant_u8(x, scale, bias)
    want = ref.dequant_u8_ref(x.reshape(-1, 3), scale, bias).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6, atol=1e-6)
