"""Paged gather-attention decode kernel: one new query token per slot
attends over K/V read *through a block table* from a shared block pool.

Layout (what :class:`repro.serving.kvcache.PagedKVCache` feeds in):

* ``k_pool``/``v_pool``: ``(num_blocks, block_size, KV, D)`` — the pool.
* ``block_tables``: ``(B, nb)`` int32 — per-slot physical block ids, in
  logical order; unused tail entries point at the reserved trash block 0.
* ``lengths``: ``(B,)`` int32 — each slot's current absolute position
  (the new token's position; K/V for it are already written), so the
  kernel masks columns ``> lengths[b]``. No left-padding: slot ``b`` pays
  attention only over its own ``lengths[b] + 1`` real positions.

The Pallas kernel gathers one ``(block_size, KV, D)`` K/V block per grid
step into VMEM via scalar-prefetched block-table indexing (the BlockSpec
index_map reads ``block_tables`` directly, so the DMA fetches exactly the
blocks the slot owns) and accumulates a numerically-stable online softmax
per (slot, kv-head), one head at a time over the block. The same kernel
serves the int8 pool of :mod:`repro.kernels.paged_attention_quant`. The
reference backend materializes the same gather with jnp indexing and runs
the exact grouped einsum the contiguous decode path uses — it is the CPU
serving oracle and the bit-identity anchor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import resolve

NEG_INF = -2.0 ** 30   # finite: exp(NEG_INF - m) underflows to exactly 0.0

__all__ = ["paged_attention", "paged_attention_ref", "paged_decode_pallas"]


# ---------------------------------------------------------------------------
# reference backend (the serving oracle on CPU)
# ---------------------------------------------------------------------------

def paged_attention_ref(q, k_pool, v_pool, block_tables, lengths, *,
                        scale: float) -> jnp.ndarray:
    """q: (B, H, D); pools: (N, bs, KV, D); block_tables: (B, nb);
    lengths: (B,). Returns (B, H, D).

    Gathers each slot's blocks to a contiguous (B, nb*bs, KV, D) view and
    runs the same grouped einsum as the contiguous decode path
    (``models.attention._grouped_attention``), so greedy tokens stay
    bit-identical to the contiguous oracle when ``nb*bs == max_len``:
    masked columns hold finite garbage whose scores are pushed to
    ``NEG_INF`` and contribute exact zeros after softmax.
    """
    b, h, d = q.shape
    kv = k_pool.shape[2]
    bs = k_pool.shape[1]
    nb = block_tables.shape[1]
    t = nb * bs
    kc = k_pool[block_tables].reshape(b, t, kv, d).astype(q.dtype)
    vc = v_pool[block_tables].reshape(b, t, kv, d).astype(q.dtype)
    valid = jnp.arange(t)[None, :] <= lengths[:, None]
    mask = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    mask = mask[:, None, None, None, :]                 # (B,1,1,S=1,T)
    rep = h // kv
    qg = q[:, None].reshape(b, 1, kv, rep, d)
    scores = jnp.einsum("bskrd,btkd->bkrst", qg, kc) * scale
    scores = scores.astype(jnp.float32)
    scores = scores + mask
    p = jax.nn.softmax(scores, axis=-1).astype(vc.dtype)
    out = jnp.einsum("bkrst,btkd->bskrd", p, vc)
    return out.reshape(b, 1, h, d)[:, 0]


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

_LANES = 128   # replicate the (rep,) softmax stats across one vreg of lanes


def _online_softmax_step(s, v, m_ref, l_ref, acc_ref, h):
    """Fold one block's masked scores ``s`` (rep, bs) and values ``v``
    (bs, D) into head ``h``'s running max, denominator and accumulator."""
    m_prev = m_ref[h]                                  # (rep, LANES)
    m_blk = jnp.max(s, axis=1, keepdims=True)          # (rep, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_blk, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)                    # lane-replicated
    p = jnp.exp(s - m_new[:, :1])                      # (rep, bs)
    l_ref[h] = alpha * l_ref[h] + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), m_prev.shape)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[h] = acc_ref[h] * alpha[:, :1] + pv
    m_ref[h] = m_new


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *refs,
                         block_size: int, scale: float, quantized: bool):
    """Grid (B, nb): one physical block per step, all KV heads at once.

    The K/V block is ``(block_size, KV, D)``; head ``h``'s ``(block_size,
    D)`` tile is a strided sublane read of it. An int8 pool also brings
    the block's ``(block_size, KV)`` scales: head ``h``'s column scales
    its code tiles in VMEM (dequantized tiles never reach HBM). The
    online softmax runs per head across the nb (innermost, sequential)
    axis."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv, rep = q_ref.shape[1], q_ref.shape[2]
    cols = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (rep, block_size), 1)
    valid = cols <= len_ref[b]
    for h in range(kv):
        q = q_ref[0, h].astype(jnp.float32)            # (rep, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)      # (bs, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)      # (bs, D)
        if quantized:
            k = k * ks_ref[0, :, h:h + 1]              # (bs, 1) per row
            v = v * vs_ref[0, :, h:h + 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)
        _online_softmax_step(s, v, m_ref, l_ref, acc_ref, h)

    @pl.when(j == nb - 1)
    def _done():
        for h in range(kv):
            o_ref[0, h] = (acc_ref[h] / l_ref[h][:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_pallas(q, k_pool, v_pool, block_tables, lengths,
                        scales=None, *, scale: float, interpret: bool):
    """Pallas paged decode over an fp pool, or over an int8 pool when
    ``scales`` is ``(k_scale, v_scale)``, each ``(N, bs, KV)``.

    Every block spans whole trailing dims — ``(1, bs, KV, D)`` for K/V,
    ``(1, bs, KV)`` for scales — which is what the TPU tiling rule admits
    for a ``(N, bs, KV, D)`` pool: a one-head ``(1, bs, 1, D)`` block puts
    1 against KV in the second-to-last dim."""
    b, h, d = q.shape
    n, bs, kv, _ = k_pool.shape
    nb = block_tables.shape[1]
    rep = h // kv
    qg = q.reshape(b, kv, rep, d)

    def row(bi, ji, bt, ln):
        return (bi, 0, 0, 0)

    def pool_block(bi, ji, bt, ln):
        return (bt[bi, ji], 0, 0, 0)

    def scale_block(bi, ji, bt, ln):
        return (bt[bi, ji], 0, 0)

    in_specs = [pl.BlockSpec((1, kv, rep, d), row),
                pl.BlockSpec((1, bs, kv, d), pool_block),
                pl.BlockSpec((1, bs, kv, d), pool_block)]
    if scales is not None:
        in_specs += [pl.BlockSpec((1, bs, kv), scale_block)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv, rep, d), row),
        scratch_shapes=[
            pltpu.VMEM((kv, rep, _LANES), jnp.float32),
            pltpu.VMEM((kv, rep, _LANES), jnp.float32),
            pltpu.VMEM((kv, rep, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, block_size=bs,
                               scale=scale, quantized=scales is not None)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rep, d), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, qg, k_pool, v_pool, *(scales or ()))
    return out.reshape(b, h, d)


# ---------------------------------------------------------------------------
# public dispatch (same policy as kernels.ops)
# ---------------------------------------------------------------------------

def paged_attention(q, k_pool, v_pool, block_tables, lengths, *,
                    scale: float, use_pallas: str = "auto") -> jnp.ndarray:
    """Block-table decode attention. ``use_pallas``: 'auto' (TPU→pallas,
    CPU→ref), 'ref', 'pallas', or 'interpret' (kernel body on CPU)."""
    use_pallas = resolve(use_pallas)
    if use_pallas != "ref":
        return paged_decode_pallas(
            q, k_pool, v_pool, block_tables, lengths, scale=scale,
            interpret=(use_pallas == "interpret"))
    return paged_attention_ref(q, k_pool, v_pool, block_tables, lengths,
                               scale=scale)
