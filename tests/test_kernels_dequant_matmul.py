"""Pallas dequant_matmul kernel vs pure-jnp oracle: int8/int4, per-channel
and per-group scales, shape sweeps, interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.squant import SQuantConfig, squant
from repro.kernels import ops, ref
from repro.kernels.dequant_matmul import dequant_matmul_pallas
from repro.quant.qtypes import pack_int4


def _quant(rng, m, n, bits, group_scales=False, group_size=32):
    codes = rng.integers(-(2 ** (bits - 1) - 1), 2 ** (bits - 1),
                         size=(m, n)).astype(np.int8)
    if group_scales:
        scale = rng.uniform(0.01, 0.1, size=(m, n // group_size)
                            ).astype(np.float32)
    else:
        scale = rng.uniform(0.01, 0.1, size=(m, 1)).astype(np.float32)
    data = np.asarray(pack_int4(jnp.asarray(codes))) if bits <= 4 else codes
    return jnp.asarray(data), jnp.asarray(scale), codes


@pytest.mark.parametrize("b,m,n,g", [
    (8, 16, 64, 32),
    (4, 32, 128, 32),
    (16, 8, 256, 64),
    (2, 128, 128, 128),
    (1, 4, 32, 32),
])
@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("group_scales", [False, True])
def test_matches_ref(rng, b, m, n, g, bits, group_scales):
    data, scale, codes = _quant(rng, m, n, bits, group_scales, g)
    x = jnp.asarray(rng.normal(size=(b, n)).astype(np.float32))
    got = dequant_matmul_pallas(x, data, scale, bits=bits,
                                tb=min(8, b), tm=min(8, m), interpret=True)
    want = ref.dequant_matmul_ref(x, data, scale, bits=bits, group_size=g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_matches_dense_matmul(rng):
    """End-to-end: x @ dequant(W).T computed three ways."""
    w = rng.normal(size=(32, 128)).astype(np.float32)
    qt, _ = squant(jnp.asarray(w), SQuantConfig(bits=4, group_size=32))
    x = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
    dense = np.asarray(x) @ np.asarray(qt.dequantize()).T
    via_ops = ops.dequant_matmul(x, qt, use_pallas="interpret")
    via_ref = ops.dequant_matmul(x, qt, use_pallas="ref")
    np.testing.assert_allclose(np.asarray(via_ops), dense, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(via_ref), dense, rtol=1e-4,
                               atol=1e-4)


def test_bf16_activations(rng):
    data, scale, _ = _quant(rng, 16, 64, 8)
    x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    got = dequant_matmul_pallas(x, data, scale, bits=8, tb=8, tm=8,
                                interpret=True)
    want = ref.dequant_matmul_ref(x, data, scale, bits=8, group_size=32)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=2e-2,
                               atol=2e-2)


def test_int4_packing_consistency(rng):
    """The kernel's in-VMEM nibble planes are the even and odd columns of
    qtypes.unpack_int4."""
    from repro.kernels.dequant_matmul import _split_nibbles
    from repro.quant.qtypes import unpack_int4
    codes = rng.integers(-8, 8, size=(4, 32)).astype(np.int8)
    packed = pack_int4(jnp.asarray(codes))
    even, odd = _split_nibbles(packed)
    full = np.asarray(unpack_int4(packed))
    np.testing.assert_array_equal(np.asarray(even), full[:, 0::2])
    np.testing.assert_array_equal(np.asarray(odd), full[:, 1::2])
    np.testing.assert_array_equal(full, codes)


@pytest.mark.parametrize("bits", [4, 8])
def test_ops_pads_batch_and_partial_m_tile(rng, bits):
    """A batch that is not a multiple of the sublane tile is padded (not
    tile-shrunk), and an M the row tile does not divide leaves a partial
    last tile whose out-of-range rows are dropped."""
    from repro.quant.qtypes import QuantizedTensor
    m, n, b = 20, 512, 11
    data, scale, _ = _quant(rng, m, n, bits)
    qt = QuantizedTensor(data=data, scale=scale, bits=bits, shape=(m, n))
    x = jnp.asarray(rng.normal(size=(b, n)).astype(np.float32))
    got = ops.dequant_matmul(x, qt, use_pallas="interpret", tm=8)
    want = ops.dequant_matmul(x, qt, use_pallas="ref")
    assert got.shape == (b, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
