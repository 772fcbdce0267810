"""Tests for the quantized serving format (``QuantizedTensor`` leaves on the
unrolled layer stack, as a real-code ``ServeEngine`` serves them):
numerical agreement with the dense model, prefill and decode.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build_model
from repro.quant.qtypes import QuantizedTensor
from repro.serving.engine import ServeConfig
from repro.serving.weights import make_weight_pipeline


def _model(arch="granite-3-8b"):
    cfg = get_config(arch, reduced=True)
    cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0)), cfg


def _serving(model, params, bits):
    """``(unrolled model, QuantizedTensor tree)`` a w``bits`` engine serves."""
    qmodel, quantize_fn, _ = make_weight_pipeline(model, ServeConfig(
        quantize_weights="squant", weight_bits=bits,
        dequantize_for_compute=False))
    return qmodel, quantize_fn(params)[0]


def test_dequant_roundtrip_w8():
    model, params, _ = _model()
    _, q = _serving(model, params, 8)
    # one kernel of the first period: dequantized codes vs dense
    qt = q["stack"]["list"][0]["b0"]["attn"]["wq"]["w"]
    assert isinstance(qt, QuantizedTensor)
    w_dense = params["stack"]["periods"]["b0"]["attn"]["wq"]["w"][0]
    w_deq = qt.dequantize(jnp.float32)                # (out, in)
    want = w_dense.T
    err = np.abs(np.asarray(w_deq) - np.asarray(want))
    assert err.max() < np.abs(np.asarray(want)).max() / 50


@pytest.mark.parametrize("arch", ["granite-3-8b", "mixtral-8x7b",
                                  "rwkv6-1.6b"])
def test_quantized_forward_close(arch):
    """w8 quantized serving tree produces near-dense logits on the
    unrolled stack (decode path included)."""
    model, params, cfg = _model(arch)
    qmodel, q8 = _serving(model, params, 8)
    batch = {"tokens": jnp.asarray([[5, 6, 7, 9]], jnp.int32)}
    c1 = model.init_cache(1, 8)
    c2 = qmodel.init_cache(1, 8)
    l1, c1 = jax.jit(model.prefill)(params, batch, c1)
    l2, c2 = jax.jit(qmodel.prefill)(q8, batch, c2)
    scale = float(np.abs(np.asarray(l1)).max())
    assert float(np.abs(np.asarray(l1 - l2)).max()) < 0.08 * scale
    tok = jnp.asarray([[3]], jnp.int32)
    d1, _ = jax.jit(model.decode_step)(params, tok, c1)
    d2, _ = jax.jit(qmodel.decode_step)(q8, tok, c2)
    assert float(np.abs(np.asarray(d1 - d2)).max()) < 0.08 * scale


def test_w4_forward_runs():
    model, params, _ = _model()
    qmodel, q4 = _serving(model, params, 4)
    batch = {"tokens": jnp.asarray([[1, 2, 3, 4]], jnp.int32)}
    cache = qmodel.init_cache(1, 8)
    logits, _ = jax.jit(qmodel.prefill)(q4, batch, cache)
    assert np.all(np.isfinite(np.asarray(logits)))
