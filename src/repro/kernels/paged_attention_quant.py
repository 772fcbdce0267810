"""Fused int8 dequant paged-attention decode kernel.

The quantized sibling of :mod:`repro.kernels.paged_attention`: the block
pool stores int8 K/V codes plus per-(position, kv-head) fp32 scales
(``(num_blocks, block_size, KV)``), written by the same
``models.attention._quant_tok`` quantizer the contiguous backend uses.
Dequantization happens *inside VMEM* after the scalar-prefetched
block-table gather — the ``kernels/dequant_matmul.py`` idiom applied to
attention:

* each step copies a group of physical blocks' ``(bs, KV, D)`` int8
  codes and their ``(bs, KV)`` scales, and dequantizes head ``h``'s code
  tiles in VMEM (``codes * scale`` per row) before the QK and PV dots.
  The kernel is :func:`repro.kernels.paged_attention.paged_decode_pallas`
  with ``scales`` given: one gather and one online softmax for both pool
  dtypes.

HBM traffic per decode token drops to ``2*D + 8`` bytes per (position,
kv-head) from ``2*D*itemsize`` for the fp pool — ~3.8x vs fp32, ~1.9x
vs bf16 — with no separate dequant materialization pass.

The jnp reference backend folds the scales into the scores and the
softmax weights instead (codes dot → k-scale fold → mask → softmax →
v-scale fold → codes dot): the same products in another order, so the
two agree to float rounding, not bitwise. It is the CPU serving oracle;
interpret-mode parity is asserted in ``tests/test_kernels_paged_quant.py``.
Greedy tokens from this path are NOT bit-identical to the fp paged
oracle — the tolerance-equivalence harness
(:mod:`repro.serving.equivalence`) budgets the divergence instead
(greedy-token agreement >= 0.98 per config).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ops import resolve
from repro.kernels.paged_attention import NEG_INF, paged_decode_pallas

__all__ = ["paged_attention_quant", "paged_attention_quant_ref"]


# ---------------------------------------------------------------------------
# reference backend (the quantized serving oracle on CPU)
# ---------------------------------------------------------------------------

def paged_attention_quant_ref(q, k_pool, v_pool, k_scale, v_scale,
                              block_tables, lengths, *,
                              scale: float) -> jnp.ndarray:
    """q: (B, H, D); code pools: (N, bs, KV, D) int8; scale pools:
    (N, bs, KV) fp32; block_tables: (B, nb); lengths: (B,).
    Returns (B, H, D).

    Same gather as :func:`paged_attention_ref`, with the scales folded
    into the scores and weights: scores = (q · codes) * softmax_scale *
    k_scale, then out = (softmax(scores) * v_scale) · v_codes (the kernel
    dequantizes the tiles first; equal up to float rounding). Never-written
    pool positions carry zero scales AND sit past ``lengths`` — the finite
    ``NEG_INF`` mask pushes them to exact-zero softmax weight.
    """
    b, h, d = q.shape
    kv = k_pool.shape[2]
    bs = k_pool.shape[1]
    nb = block_tables.shape[1]
    t = nb * bs
    kc = k_pool[block_tables].reshape(b, t, kv, d).astype(jnp.float32)
    vc = v_pool[block_tables].reshape(b, t, kv, d).astype(jnp.float32)
    ks = k_scale[block_tables].reshape(b, t, kv).transpose(0, 2, 1)
    vs = v_scale[block_tables].reshape(b, t, kv).transpose(0, 2, 1)
    valid = jnp.arange(t)[None, :] <= lengths[:, None]       # (B, T)
    rep = h // kv
    qg = q.astype(jnp.float32).reshape(b, kv, rep, d)
    scores = jnp.einsum("bkrd,btkd->bkrt", qg, kc) * scale
    scores = scores * ks[:, :, None, :]                      # fold k_scale
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pw = p * vs[:, :, None, :]                               # fold v_scale
    out = jnp.einsum("bkrt,btkd->bkrd", pw, vc) / l
    return out.astype(q.dtype).reshape(b, h, d)


# ---------------------------------------------------------------------------
# public dispatch (same policy as kernels.paged_attention)
# ---------------------------------------------------------------------------

def paged_attention_quant(q, k_pool, v_pool, k_scale, v_scale,
                          block_tables, lengths, *, scale: float,
                          use_pallas: str = "auto") -> jnp.ndarray:
    """Fused int8-dequant block-table decode attention. ``use_pallas``:
    'auto' (TPU→pallas, CPU→ref), 'ref', 'pallas', or 'interpret'."""
    use_pallas = resolve(use_pallas)
    if use_pallas != "ref":
        return paged_decode_pallas(
            q, k_pool, v_pool, block_tables, lengths, (k_scale, v_scale),
            scale=scale, interpret=(use_pallas == "interpret"))
    return paged_attention_quant_ref(q, k_pool, v_pool, k_scale, v_scale,
                                     block_tables, lengths, scale=scale)
