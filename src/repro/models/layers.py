"""Primitive layers: norms, embeddings, rotary, quant-aware dense.

Params are plain nested dicts. Kernels are named ``w`` with shape (in, out)
(the quantization pipeline and sharding rules key off these conventions).
``linear`` transparently consumes a QuantizedTensor (SQuant serving format,
(out, in)-major) — dequant-on-the-fly via the Pallas kernel on TPU or the
jnp reference elsewhere.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.quant.qtypes import QuantizedTensor


def _init_dense(key, d_in: int, d_out: int, dtype=jnp.float32,
                scale: Optional[float] = None):
    s = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    return {"w": (jax.random.normal(key, (d_in, d_out), dtype) * s)}


def linear(params, x: jnp.ndarray, use_kernel: str = "auto") -> jnp.ndarray:
    """x @ W, for ``{"w": (in, out) float}`` (dense) or
    ``{"w": QuantizedTensor}`` (dequant-on-the-fly matmul)."""
    w = params["w"]
    if isinstance(w, QuantizedTensor):
        from repro.kernels import ops                     # lazy import
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        y = ops.dequant_matmul(x2, w, use_pallas=use_kernel)
        return y.reshape(*lead, -1)
    return x @ w.astype(x.dtype)


def rms_norm(params, x: jnp.ndarray, eps: float = 1e-6,
             plus_one: bool = False) -> jnp.ndarray:
    """RMSNorm. ``plus_one=True`` uses the Gemma (1+g) parameterization."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    g = params["gain"].astype(jnp.float32)
    g = 1.0 + g if plus_one else g
    return (xf * g).astype(dt)


def layer_norm(params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (xf * params["gain"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


def init_norm(d: int, kind: str = "rms", plus_one: bool = False):
    if kind == "rms":
        gain = jnp.zeros((d,), jnp.float32) if plus_one else \
            jnp.ones((d,), jnp.float32)
        return {"gain": gain}
    return {"gain": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def embed(params, tokens: jnp.ndarray) -> jnp.ndarray:
    return params["embedding"][tokens]


def init_embedding(key, vocab: int, d: int, dtype=jnp.float32):
    return {"embedding": jax.random.normal(key, (vocab, d), dtype) * 0.02}


def rotary_tables(head_dim: int, max_len: int, theta: float = 10000.0,
                  dtype=jnp.float32):
    """(cos, sin) tables of shape (max_len, head_dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
                 ) -> jnp.ndarray:
    """x: (B, S, H, D); cos/sin: (S, D/2) shared across the batch, or
    (B, S, D/2) per-row (paged decode: each slot sits at its own absolute
    position). cos/sin cast to x.dtype so rotary never promotes bf16
    activations."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    if cos.ndim == 3:
        c = cos.astype(x.dtype)[:, :, None, :]
        s = sin.astype(x.dtype)[:, :, None, :]
    else:
        c = cos.astype(x.dtype)[None, :, None, :]
        s = sin.astype(x.dtype)[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 ignore_id: int = -1) -> jnp.ndarray:
    """Mean cross-entropy over non-ignored positions; fp32 internally."""
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None].clip(0), axis=-1)[..., 0]
    mask = (labels != ignore_id).astype(jnp.float32)
    nll = (lse - ll) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)
