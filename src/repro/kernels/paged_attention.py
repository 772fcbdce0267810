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

The Pallas kernel walks one slot per grid step and, inside it, only the
pages the slot's length reaches, :func:`pages_per_step` pages at a time:
the pools stay in HBM and each group's pages are copied by hand through
the scalar-prefetched block table into a double buffer, the next group's
copies running while this group is attended. It accumulates a
numerically-stable online softmax per (slot, kv-head), one head at a time
over the group. :func:`positions_walked` counts what a call walks, on
either backend. The same kernel serves the int8 pool of
:mod:`repro.kernels.paged_attention_quant`. The reference backend
materializes the same gather with jnp indexing and runs the exact grouped
einsum the contiguous decode path uses — it is the CPU serving oracle and
the bit-identity anchor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import resolve

NEG_INF = -2.0 ** 30   # finite: exp(NEG_INF - m) underflows to exactly 0.0

__all__ = ["live_groups", "pages_per_step", "paged_attention",
           "paged_attention_ref", "paged_decode_pallas", "positions_walked"]


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
# the walk: groups of pages, as far as each slot's length reaches
# ---------------------------------------------------------------------------

_STEP_POSITIONS = 256   # positions one kernel step attends, at most
_STEP_HEAD_ROWS = 1024  # (position, KV head) rows per step, at most
_STEP_BYTES = 1 << 20   # K+V bytes one step copies, at most


def pages_per_step(bs: int, kv: int, d: int, itemsize: int, nb: int) -> int:
    """Pages the kernel copies and attends per step, from shapes alone: at
    most 256 positions, at most 1024 (position, KV head) rows (a step's
    heads run one after another), at most 1 MB of K and V (pages ``(bs,
    kv, d)`` of ``itemsize`` bytes), at most the table width ``nb``. Chat's
    GQA pool (8 KV heads) takes 8 pages, MLA's latent rows 16."""
    page = 2 * bs * kv * d * itemsize
    return max(1, min(_STEP_POSITIONS // bs, _STEP_HEAD_ROWS // (bs * kv),
                      _STEP_BYTES // page, nb))


def live_groups(lengths, block_size: int, pages: int):
    """Groups of ``pages`` pages the kernel walks for a slot at position
    ``lengths``: its ``lengths // block_size + 1`` pages, rounded up to
    whole groups (a retired slot, at 0, walks one)."""
    return lengths // (block_size * pages) + 1


def positions_walked(lengths, nb: int, *, block_size: int, kv: int, d: int,
                     itemsize: int) -> int:
    """Pool positions one :func:`paged_attention` call walks, over all its
    rows (host ``lengths``, tables ``nb`` wide), on the backend ``"auto"``
    resolves to: the reference gathers every table to its end; the kernel
    walks each row's live groups."""
    if resolve("auto") == "ref":
        return len(lengths) * nb * block_size
    p = pages_per_step(block_size, kv, d, itemsize, nb)
    groups = live_groups(np.asarray(lengths, np.int64), block_size, p)
    return int(groups.sum()) * p * block_size


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

_LANES = 128   # replicate the (rep,) softmax stats across one vreg of lanes


def _online_softmax_step(s, v, m_ref, l_ref, acc_ref, h):
    """Fold one group's masked scores ``s`` (rep, T) and values ``v``
    (T, D) into head ``h``'s running max, denominator and accumulator."""
    m_prev = m_ref[h]                                  # (rep, LANES)
    m_blk = jnp.max(s, axis=1, keepdims=True)          # (rep, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_blk, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)                    # lane-replicated
    p = jnp.exp(s - m_new[:, :1])                      # (rep, T)
    l_ref[h] = alpha * l_ref[h] + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), m_prev.shape)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[h] = acc_ref[h] * alpha[:, :1] + pv
    m_ref[h] = m_new


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, *refs,
                         block_size: int, pages: int, scale: float,
                         quantized: bool):
    """Grid (B,): slot ``b``'s live pages, ``pages`` at a time.

    The pools stay in HBM. A group's pages are copied by hand, through the
    scalar-prefetched block table, into one half of a double buffer of
    ``pages * block_size`` rows while the previous group is attended from
    the other half; a slot's last group starts the next slot's first. A
    group copies the slot's pages from ``g * pages`` up to its last live
    one (``lengths[b] // block_size``), so the walk stops where the slot's
    length does. Head ``h``'s ``(T, D)`` tile is a strided sublane read of
    the buffer (the whole buffer for a one-head pool); an int8 pool brings
    its pages' scales too, applied in VMEM. The online softmax runs per
    head across the slot's groups."""
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem, slot_ref,
         m_ref, l_ref, acc_ref) = refs
        pairs = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                 (vs_hbm, vs_buf))
    else:
        o_ref, k_buf, v_buf, sem, slot_ref, m_ref, l_ref, acc_ref = refs
        pairs = ((k_hbm, k_buf), (v_hbm, v_buf))
    nbatch, nb = bt_ref.shape
    kv, rep = q_ref.shape[1], q_ref.shape[2]
    span = pages * block_size
    b = pl.program_id(0)

    def n_pages(bi):
        return jnp.minimum(len_ref[bi] // block_size + 1, nb)

    def copies(bi, g, slot, start: bool):
        first = g * pages

        def page(col, carry):
            phys = bt_ref[bi, col]
            row = pl.multiple_of((col - first) * block_size, block_size)
            for hbm, buf in pairs:
                cp = pltpu.make_async_copy(
                    hbm.at[phys], buf.at[slot, pl.ds(row, block_size)],
                    sem.at[slot])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(first, jnp.minimum(first + pages, n_pages(bi)),
                          page, 0)

    def tile(buf, slot, h):
        return (buf[slot] if buf.ndim == 3 else buf[slot, :, h, :]) \
            .astype(jnp.float32)

    def group(g, carry):
        slot = slot_ref[0]
        last = g + 1 == n_groups
        nxt = jnp.where(last, b + 1, b)

        @pl.when(nxt < nbatch)
        def _prefetch():
            copies(nxt, jnp.where(last, 0, g + 1), 1 - slot, True)

        copies(b, g, slot, False)
        base = g * span
        valid = base + jax.lax.broadcasted_iota(
            jnp.int32, (rep, span), 1) <= length
        # rows past the slot's last live page were not copied for this
        # group: they hold whatever the buffer held, NaN included
        live_rows = base + jax.lax.broadcasted_iota(
            jnp.int32, (span, 1), 0) <= length
        for h in range(kv):
            q = q_ref[0, h].astype(jnp.float32)        # (rep, D)
            k = tile(k_buf, slot, h)                   # (T, D)
            v = tile(v_buf, slot, h)
            if quantized:
                k = k * ks_buf[slot, :, h:h + 1]       # (T, 1) per row
                v = v * vs_buf[slot, :, h:h + 1]
            v = jnp.where(live_rows, v, 0.0)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * scale, NEG_INF)
            _online_softmax_step(s, v, m_ref, l_ref, acc_ref, h)
        slot_ref[0] = 1 - slot
        return carry

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        copies(0, 0, 0, True)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    length = len_ref[b]
    n_groups = live_groups(jnp.minimum(length, nb * block_size - 1),
                           block_size, pages)
    jax.lax.fori_loop(0, n_groups, group, 0)
    for h in range(kv):
        o_ref[0, h] = (acc_ref[h] / l_ref[h][:, :1]).astype(o_ref.dtype)


def _pad_last(x, to: int):
    pad = -x.shape[-1] % to
    return x if not pad else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _copyable(pool):
    """The pool as the kernel copies it, a page at a time. Mosaic copies a
    page only if its last dim fills whole 128-lane tiles and its KV dim
    whole packed rows: a one-head pool drops its KV dim, a KV dim short of
    the packing and a width off the lane tile (MLA's 576) are padded,
    which copies the pool."""
    n, bs, kv, d = pool.shape
    if kv == 1:
        return _pad_last(pool.reshape(n, bs, d), _LANES)
    pack = 4 // pool.dtype.itemsize
    if kv % pack:
        pool = jnp.pad(pool, ((0, 0), (0, 0), (0, -kv % pack), (0, 0)))
    return _pad_last(pool, _LANES)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_pallas(q, k_pool, v_pool, block_tables, lengths,
                        scales=None, *, scale: float, interpret: bool):
    """Pallas paged decode over an fp pool, or over an int8 pool when
    ``scales`` is ``(k_scale, v_scale)``, each ``(N, bs, KV)``.

    The pools are handed over in HBM (``pl.ANY``) and copied a page at a
    time by the kernel, :func:`pages_per_step` pages a step."""
    b, h, d = q.shape
    n, bs, kv, _ = k_pool.shape
    nb = block_tables.shape[1]
    rep = h // kv
    p = pages_per_step(bs, kv, d, k_pool.dtype.itemsize, nb)
    qg = _pad_last(q.reshape(b, kv, rep, d), _LANES)
    dp = qg.shape[-1]
    pools = [_copyable(k_pool), _copyable(v_pool)]
    bufs = [pltpu.VMEM((2, p * bs) + pools[0].shape[2:], k_pool.dtype)] * 2
    if scales is not None:
        pools += [_pad_last(s_, _LANES) for s_ in scales]
        bufs += [pltpu.VMEM((2, p * bs) + pools[2].shape[2:],
                            scales[0].dtype)] * 2
    row = pl.BlockSpec((1, kv, rep, dp), lambda bi, bt, ln: (bi, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[row] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=row,
        scratch_shapes=bufs + [
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((kv, rep, _LANES), jnp.float32),
            pltpu.VMEM((kv, rep, _LANES), jnp.float32),
            pltpu.VMEM((kv, rep, dp), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, block_size=bs, pages=p,
                               scale=scale, quantized=scales is not None)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rep, dp), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables, lengths, qg, *pools)
    return out[..., :d].reshape(b, h, d)


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
