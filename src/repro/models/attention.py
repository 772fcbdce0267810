"""Attention: GQA / MQA / sliding-window / MLA, with KV caches for decode.

Grouped-query attention uses the grouped einsum form (no materialized KV
repeat). Sliding-window decode keeps a ring-buffer cache of window size
(O(window) state — the sub-quadratic path mixtral uses for long contexts).
MLA (MiniCPM3/DeepSeek-style) caches one latent row per position (normed
c_kv ‖ the shared RoPE key), re-expanding K/V or attending in the latent
space (absorbed; the paged pool always does).

KV caches optionally store int8 codes with per-(token, head) scales
(``quantize_kv``): a serving-memory optimization SQuant's weight format pairs
with (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_act
from repro.models.layers import _init_dense, apply_rotary, init_norm, linear, rms_norm

NEG_INF = -2.0 ** 30


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora: Optional[int] = 768         # None: one direct q projection
    kv_lora: int = 256
    nope_dim: int = 64
    rope_dim: int = 32
    v_dim: int = 64
    # Decode-time weight absorption (DeepSeek-style): fold kv_up's key half
    # into the query and its value half into the output, so attention runs
    # directly against the compressed cache — O(S·kv_lora·H) per step
    # instead of O(S·kv_lora·H·(nope+v)) for re-expanding the cache.
    absorb: bool = False


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(key, cfg) -> Dict[str, Any]:
    d = cfg.d_model
    hd = cfg.head_dim
    keys = jax.random.split(key, 8)
    if cfg.mla is not None:
        m = cfg.mla
        qk_dim = m.nope_dim + m.rope_dim
        if m.q_lora is None:
            p = {"wq": _init_dense(keys[0], d, cfg.n_heads * qk_dim)}
        else:
            p = {"q_down": _init_dense(keys[0], d, m.q_lora),
                 "q_norm": init_norm(m.q_lora),
                 "q_up": _init_dense(keys[1], m.q_lora,
                                     cfg.n_heads * qk_dim)}
        p.update({
            "kv_down": _init_dense(keys[2], d, m.kv_lora + m.rope_dim),
            "kv_norm": init_norm(m.kv_lora),
            "kv_up": _init_dense(keys[3], m.kv_lora,
                                 cfg.n_heads * (m.nope_dim + m.v_dim)),
            "wo": _init_dense(keys[4], cfg.n_heads * m.v_dim, d),
        })
        return p
    p = {
        "wq": _init_dense(keys[0], d, cfg.n_heads * hd),
        "wk": _init_dense(keys[1], d, cfg.n_kv_heads * hd),
        "wv": _init_dense(keys[2], d, cfg.n_kv_heads * hd),
        "wo": _init_dense(keys[3], cfg.n_heads * hd, d),
    }
    if cfg.qk_norm:
        p["q_ln"] = init_norm(hd)
        p["k_ln"] = init_norm(hd)
    return p


def init_cross_attention(key, cfg) -> Dict[str, Any]:
    d = cfg.d_model
    hd = cfg.head_dim
    keys = jax.random.split(key, 4)
    return {
        "wq": _init_dense(keys[0], d, cfg.n_heads * hd),
        "wk": _init_dense(keys[1], d, cfg.n_heads * hd),
        "wv": _init_dense(keys[2], d, cfg.n_heads * hd),
        "wo": _init_dense(keys[3], cfg.n_heads * hd, d),
    }


# ---------------------------------------------------------------------------
# KV cache (optionally int8)
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16, quantize: bool = False,
                  window: Optional[int] = None) -> Dict[str, Any]:
    slots = min(max_len, window) if window else max_len
    shape = (batch, slots, n_kv, head_dim)
    if quantize:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3], jnp.float32),
                "v_scale": jnp.zeros(shape[:3], jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quant_tok(x):
    """(..., KV, D) → int8 codes + per-(..., KV) scale.

    Per-(token, head) absmax scales with a 1e-6 floor, so all-zero rows
    quantize to exact zeros instead of 0/0 NaNs. Codes are clipped to
    [-127, 127] before the int8 cast: ``round(amax / scale)`` can land on
    128.0 under fp rounding, which would wrap to -128 — flipping the
    row's largest-magnitude element to the wrong sign. Pure elementwise +
    one reduction over the trailing axis, so it vmaps/jits over any
    leading shape (both serving backends share this one quantizer)."""
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    codes = jnp.clip(jnp.round(x / scale[..., None]),
                     -127.0, 127.0).astype(jnp.int8)
    return codes, scale.astype(jnp.float32)


def _cache_write(cache, k, v, pos, window):
    """Write new k/v (B, S, KV, D) at absolute position ``pos``."""
    slots = cache["k"].shape[1]
    s = k.shape[1]
    if window and s >= slots:
        # ring buffer: keep the last ``slots`` tokens, each at slot p%slots
        shift = (pos + s) % slots
        k = jnp.roll(k[:, -slots:], shift, axis=1)
        v = jnp.roll(v[:, -slots:], shift, axis=1)
        idx = 0
    else:
        idx = (pos % slots) if window else pos
    quant = "k_scale" in cache
    if quant:
        kq, ks = _quant_tok(k)
        vq, vs = _quant_tok(v)
        cache = dict(cache)
        cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, idx, 1)
        cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, idx, 1)
        cache["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks, idx, 1)
        cache["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs, idx, 1)
        return cache
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), idx, 1)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), idx, 1)
    return cache


def _ring_scatter(cache, k, v, pos):
    """Scatter-write a chunk of ``s`` tokens into a ring-buffer cache at
    slots ``(pos + i) % slots`` (``pos`` may be traced). Unlike
    ``_cache_write``'s contiguous ``dynamic_update_slice`` (which clamps at
    the cache edge instead of wrapping), this handles a chunk that straddles
    the ring boundary."""
    slots = cache["k"].shape[1]
    if k.shape[1] >= slots:
        # chunk wider than the ring: only the newest ``slots`` tokens
        # survive; dropping the rest keeps ``idx`` duplicate-free
        # (scatter-set order is unspecified under duplicates)
        off = k.shape[1] - slots
        k, v, pos = k[:, off:], v[:, off:], pos + off
    idx = (pos + jnp.arange(k.shape[1])) % slots
    cache = dict(cache)
    if "k_scale" in cache:
        kq, ks = _quant_tok(k)
        vq, vs = _quant_tok(v)
        cache["k"] = cache["k"].at[:, idx].set(kq)
        cache["v"] = cache["v"].at[:, idx].set(vq)
        cache["k_scale"] = cache["k_scale"].at[:, idx].set(ks)
        cache["v_scale"] = cache["v_scale"].at[:, idx].set(vs)
        return cache
    cache["k"] = cache["k"].at[:, idx].set(k.astype(cache["k"].dtype))
    cache["v"] = cache["v"].at[:, idx].set(v.astype(cache["v"].dtype))
    return cache


def _cache_read(cache) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if "k_scale" in cache:
        k = cache["k"].astype(jnp.float32) * cache["k_scale"][..., None]
        v = cache["v"].astype(jnp.float32) * cache["v_scale"][..., None]
        return k, v
    return cache["k"], cache["v"]


# ---------------------------------------------------------------------------
# core attention math
# ---------------------------------------------------------------------------

def _grouped_attention(q, k, v, mask, softmax_scale) -> jnp.ndarray:
    """q: (B,S,H,D), k/v: (B,T,KV,Dv); H = KV * rep. mask: (S,T) or
    (B,1,1,S,T) additive. Used for decode (S small): no KV repeat."""
    b, s, h, dq = q.shape
    t, kv = k.shape[1], k.shape[2]
    rep = h // kv
    qg = q.reshape(b, s, kv, rep, dq)
    scores = jnp.einsum("bskrd,btkd->bkrst", qg, k) * softmax_scale
    scores = scores.astype(jnp.float32)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None, None]
        scores = scores + mask
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkrst,btkd->bskrd", p, v)
    return out.reshape(b, s, h, v.shape[-1])


Q_CHUNK = 1024   # query-block size bounding the (B,H,Cq,T) score tensor


def _chunked_attention(q, k, v, *, scale, causal: bool,
                       window: Optional[int] = None,
                       q_chunk: int = Q_CHUNK, row0=0) -> jnp.ndarray:
    """Train/prefill attention: KV repeated to H heads (so scores shard over
    the TP axis) and queries processed in blocks — the (B, H, Cq, T) block,
    not (B, H, S, T), bounds the working set. Softmax sees the full key axis
    per row, so this is exact (no online-softmax merge needed).

    q: (B,S,H,D); k/v: (B,T,KV,Dv) — repeated internally when KV < H.
    ``row0`` offsets the queries' absolute positions (may be traced): chunked
    prefill passes the cache clock so a partial-prompt chunk masks against
    absolute positions while attending over the whole cache.
    """
    b, s, h, dq = q.shape
    t, kv = k.shape[1], k.shape[2]
    if kv != h:
        rep = h // kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    def block(qc, roff):
        scores = jnp.einsum("bshd,bthd->bhst", qc, k) * scale
        scores = shard_act(scores.astype(jnp.float32),
                           ("batch", "heads", None, None))
        if causal:
            rows = roff + jnp.arange(qc.shape[1])
            cols = jnp.arange(t)
            ok = cols[None, :] <= rows[:, None]
            if window is not None:
                ok &= cols[None, :] > rows[:, None] - window
            scores = jnp.where(ok[None, None], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhst,bthd->bshd", p, v)

    if s <= q_chunk or s % q_chunk != 0:
        return block(q, row0)
    nc = s // q_chunk
    qr = q.reshape(b, nc, q_chunk, h, dq).swapaxes(0, 1)
    offs = row0 + jnp.arange(nc) * q_chunk

    def body(_, qc_off):
        qc, off = qc_off
        return 0, block(qc, off)

    _, outs = jax.lax.scan(body, 0, (qr, offs))
    return outs.swapaxes(0, 1).reshape(b, s, h, v.shape[-1])


def causal_mask(s: int, t: Optional[int] = None,
                window: Optional[int] = None) -> jnp.ndarray:
    t = t or s
    qi = jnp.arange(s)[:, None] + (t - s)     # absolute query positions
    ki = jnp.arange(t)[None, :]
    ok = ki <= qi
    if window is not None:
        ok &= ki > qi - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def attention(params, x, *, cfg, rope, mode: str = "train",
              cache: Optional[dict] = None, pos: Optional[jnp.ndarray] = None,
              block_tables: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Self-attention.

    mode: "train"/"prefill" (full sequence, causal (+window) mask, prefill
    also fills the cache), "decode" (single new token against the cache), or
    "chunk" (a partial-prefill continuation: ``s`` prompt tokens written at
    absolute position ``pos``, attending over the already-filled cache
    prefix — the same repeated-KV einsum as prefill, so the chunked path's
    activations match the monolithic prefill bit-for-bit). ``pos`` is a
    traced scalar, so the same trace serves the contiguous scheduler's
    shared clock AND the paged backend's per-slot positions (a chunked
    paged admission continues from its own prompt offset, shared-prefix
    gathers included) with no per-offset retrace.

    ``block_tables`` switches decode to the paged layout: the cache leaves
    are a block pool ``(num_blocks, block_size, KV, D)`` shared by all
    slots, ``pos`` is a per-slot length vector ``(B,)``, and each slot's
    K/V is reached through its ``block_tables`` row (no left-padding; see
    :mod:`repro.kernels.paged_attention`).
    """
    if cfg.mla is not None:
        return _mla_attention(params, x, cfg=cfg, rope=rope, mode=mode,
                              cache=cache, pos=pos,
                              block_tables=block_tables)
    if mode == "verify" and cfg.window:
        raise NotImplementedError(
            "'verify' mode is not implemented for sliding-window "
            "ring-buffer caches")
    b, s, d = x.shape
    hd = cfg.head_dim
    cos_t, sin_t = rope                      # (s, hd/2) for current tokens
    q = linear(params["wq"], x).reshape(b, s, cfg.n_heads, hd)
    k = linear(params["wk"], x).reshape(b, s, cfg.n_kv_heads, hd)
    v = linear(params["wv"], x).reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(params["q_ln"], q)
        k = rms_norm(params["k_ln"], k)
    q = apply_rotary(q, cos_t, sin_t)
    k = apply_rotary(k, cos_t, sin_t)
    q = shard_act(q, ("batch", None, "heads", None))
    scale = hd ** -0.5

    if mode in ("train", "prefill"):
        out = _chunked_attention(q, k, v, scale=scale, causal=True,
                                 window=cfg.window,
                                 q_chunk=cfg.attn_q_chunk)
        if mode == "prefill":
            cache = _cache_write(cache, k, v, 0, cfg.window)
    elif mode == "chunk" and cfg.window:
        # ring-buffer continuation: the chunk's queries attend over the ring
        # *as of chunk entry* (the trailing min(pos, slots) keys)
        # concatenated with the chunk's own keys — attention runs before the
        # ring write, because writing first would evict up to s-1 in-window
        # keys the chunk's earliest rows still need. Each ring slot's
        # absolute key position is reconstructed from the clock (the largest
        # position ≡ slot (mod slots) already written; negative → never
        # written this admission → masked), so stale rows from a reused side
        # cache contribute exact zeros. The key axis is a rotation of the
        # monolithic ordering → tokens agree up to float reassociation,
        # served under the "sliding_window" agreement budget.
        kc, vc = _cache_read(cache)
        kc = shard_act(kc, ("batch", "seq_shard", "kv_heads", None))
        vc = shard_act(vc, ("batch", "seq_shard", "kv_heads", None))
        slots = kc.shape[1]
        j = jnp.arange(slots)
        kpos_ring = j + ((pos - j - 1) // slots) * slots
        kpos = jnp.concatenate([kpos_ring, pos + jnp.arange(s)])
        rows = pos + jnp.arange(s)
        ok = ((kpos[None, :] >= 0) & (kpos[None, :] <= rows[:, None])
              & (kpos[None, :] > rows[:, None] - cfg.window))
        mask = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
        k_all = jnp.concatenate([kc.astype(q.dtype), k.astype(q.dtype)], 1)
        v_all = jnp.concatenate([vc.astype(q.dtype), v.astype(q.dtype)], 1)
        out = _grouped_attention(q, k_all, v_all, mask, scale)
        cache = _ring_scatter(cache, k, v, pos)
    elif mode == "chunk":
        # partial-prefill continuation: write this chunk at the clock, then
        # run the prefill einsum against the whole cache with the rows'
        # absolute positions masking the unwritten suffix (zeros → exp(-inf)
        # → exact zero contributions, so the result is bit-identical to the
        # monolithic prefill of the full sequence for chunk sizes >= 2)
        cache = _cache_write(cache, k, v, pos, None)
        kc, vc = _cache_read(cache)
        kc = shard_act(kc, ("batch", "seq_shard", "kv_heads", None))
        vc = shard_act(vc, ("batch", "seq_shard", "kv_heads", None))
        out = _chunked_attention(q, kc.astype(q.dtype), vc.astype(q.dtype),
                                 scale=scale, causal=True, window=None,
                                 q_chunk=cfg.attn_q_chunk, row0=pos)
    elif mode == "verify":  # paged multi-position verify: pos is (B,)
        # speculative decoding's verifier forward: slot b's S tokens are
        # written through the block table at absolute positions
        # pos[b]..pos[b]+S-1, then every row attends over its own
        # inclusive prefix via ONE flattened paged_attention call — row
        # (b, j) becomes batch row b*S+j with length pos[b]+j, the exact
        # (query, keys, mask) triple a lockstep decode step at that
        # position would see, which is what makes greedy verify tokens
        # bit-identical to verifier-only decode
        if block_tables is None:
            raise NotImplementedError(
                "verify mode requires the paged KV layout (block tables); "
                "the contiguous cache has one shared clock and cannot "
                "score per-slot multi-position runs")
        if "k_scale" in cache:
            raise NotImplementedError(
                "verify mode requires an fp KV pool (speculative "
                "acceptance is gated off quantize_kv)")
        from repro.kernels.paged_attention import paged_attention
        bs_blk = cache["k"].shape[1]
        idx = pos[:, None] + jnp.arange(s)               # (B, S) abs pos
        rows = jnp.arange(b)
        phys = block_tables[rows[:, None], idx // bs_blk]
        off = idx % bs_blk
        cache = dict(cache)
        cache["k"] = cache["k"].at[phys, off].set(k.astype(cache["k"].dtype))
        cache["v"] = cache["v"].at[phys, off].set(v.astype(cache["v"].dtype))
        out = paged_attention(
            q.reshape(b * s, cfg.n_heads, hd), cache["k"], cache["v"],
            jnp.repeat(block_tables, s, axis=0), idx.reshape(-1),
            scale=scale).reshape(b, s, cfg.n_heads, hd)
    elif block_tables is not None:  # paged decode: s == 1, pos is (B,)
        # write the new K/V row through the table (slot b's token lands in
        # physical block ``bt[b, pos//bs]`` at offset ``pos % bs``; retired
        # slots point at the trash block and are masked out by length),
        # then attend via the gather kernel — exact-zero contributions from
        # masked columns keep tokens bit-identical to the contiguous
        # oracle at equal effective context (nb * bs == max_len). With a
        # quantized pool the new row is quantized per-(token, head) before
        # the write (the same ``_quant_tok`` the contiguous cache uses) and
        # attention runs through the fused int8-dequant kernel; tokens are
        # then tolerance-equivalent, not bit-identical (see
        # repro.serving.equivalence).
        bs_blk = cache["k"].shape[1]
        rows = jnp.arange(b)
        phys = block_tables[rows, pos // bs_blk]
        off = pos % bs_blk
        cache = dict(cache)
        if "k_scale" in cache:
            from repro.kernels.paged_attention_quant import \
                paged_attention_quant
            kq, ks = _quant_tok(k)
            vq, vs = _quant_tok(v)
            cache["k"] = cache["k"].at[phys, off].set(kq[:, 0])
            cache["v"] = cache["v"].at[phys, off].set(vq[:, 0])
            cache["k_scale"] = cache["k_scale"].at[phys, off].set(ks[:, 0])
            cache["v_scale"] = cache["v_scale"].at[phys, off].set(vs[:, 0])
            out = paged_attention_quant(
                q[:, 0], cache["k"], cache["v"], cache["k_scale"],
                cache["v_scale"], block_tables, pos, scale=scale)[:, None]
        else:
            from repro.kernels.paged_attention import paged_attention
            cache["k"] = cache["k"].at[phys, off].set(
                k[:, 0].astype(cache["k"].dtype))
            cache["v"] = cache["v"].at[phys, off].set(
                v[:, 0].astype(cache["v"].dtype))
            out = paged_attention(q[:, 0], cache["k"], cache["v"],
                                  block_tables, pos, scale=scale)[:, None]
    elif pos.ndim == 1:  # decode, per-row positions on a contiguous cache
        # the speculative drafter's cache: contiguous (max_slots, max_len)
        # rows, but slots sit at their own absolute positions (paged slots
        # are not left-padded), so the write is a per-row scatter and each
        # row masks against its own position — the same per-row semantics
        # as paged decode, without the block indirection
        if cfg.window or "k_scale" in cache:
            raise NotImplementedError(
                "per-row decode positions are not implemented for "
                "sliding-window or quantized contiguous caches")
        rows = jnp.arange(b)
        cache = dict(cache)
        cache["k"] = cache["k"].at[rows, pos].set(
            k[:, 0].astype(cache["k"].dtype))
        cache["v"] = cache["v"].at[rows, pos].set(
            v[:, 0].astype(cache["v"].dtype))
        kc, vc = _cache_read(cache)
        si = jnp.arange(kc.shape[1])
        valid = si[None, :] <= pos[:, None]               # (B, T)
        mask = jnp.where(valid, 0.0, NEG_INF)[:, None, None, None, :]
        out = _grouped_attention(q, kc.astype(q.dtype), vc.astype(q.dtype),
                                 mask, scale)
    else:  # decode: s == 1, absolute position ``pos``
        cache = _cache_write(cache, k, v, pos, cfg.window)
        kc, vc = _cache_read(cache)
        kc = shard_act(kc, ("batch", "seq_shard", "kv_heads", None))
        vc = shard_act(vc, ("batch", "seq_shard", "kv_heads", None))
        slots = kc.shape[1]
        si = jnp.arange(slots)
        if cfg.window:
            valid = (si <= (pos % slots)) | (pos >= slots)
        else:
            valid = si <= pos
        mask = jnp.where(valid, 0.0, NEG_INF)[None, None, None, None, :]
        out = _grouped_attention(q, kc.astype(q.dtype), vc.astype(q.dtype),
                                 mask, scale)
    out = out.reshape(b, s, cfg.n_heads * hd)
    return linear(params["wo"], out), cache


def _mla_attention(params, x, *, cfg, rope, mode, cache, pos,
                   block_tables=None):
    """Multi-head latent attention over a cache of latent rows.

    Each position caches one row ``kv_norm(c_kv) ‖ rope(k_rope)`` of width
    ``kv_lora + rope_dim``: ``(B, T, W)`` in a contiguous cache,
    ``(num_blocks, block_size, W)`` in a paged pool, read as one "KV
    head". ``W`` is the width rounded up to whole 128-lane tiles, the
    lanes past it zero (:func:`latent_store_width`): a row the tiles fit
    exactly keeps the pool row-major on the device, where the row scatter
    writes it in place and the decode kernel copies its pages as they
    are; a 576-wide row is stored position-minor and converted in and out
    of every step. Zero lanes add exact zeros to every score and value.
    RMS norm is per position, so the normed latent is what every read
    would compute. Train/prefill/chunk expand the latent to per-head K/V;
    decode either expands it or (``absorb``, and always on the paged pool)
    attends in the latent space (:func:`_mla_absorbed_decode`)."""
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    cos_t, sin_t = rope                      # (s, rope_dim/2)
    qk_dim = m.nope_dim + m.rope_dim

    if m.q_lora is None:
        q = linear(params["wq"], x)
    else:
        q = linear(params["q_up"],
                   rms_norm(params["q_norm"], linear(params["q_down"], x)))
    q = q.reshape(b, s, h, qk_dim)
    q_nope, q_rope = q[..., :m.nope_dim], q[..., m.nope_dim:]

    ckv_full = linear(params["kv_down"], x)            # (B,S,kv_lora+rope)
    c_kv, k_rope = ckv_full[..., :m.kv_lora], ckv_full[..., m.kv_lora:]
    k_rope = k_rope.reshape(b, s, 1, m.rope_dim)

    q_rope = apply_rotary(q_rope, cos_t, sin_t)
    k_rope = apply_rotary(k_rope, cos_t, sin_t)
    width = m.kv_lora + m.rope_dim
    lat = jnp.concatenate(
        [rms_norm(params["kv_norm"], c_kv),
         k_rope[:, :, 0].astype(c_kv.dtype)], axis=-1)  # (B,S,L+R)
    lat_row = jnp.pad(lat, ((0, 0), (0, 0),
                            (0, latent_store_width(m) - width)))

    def wide(qa):                        # absorbed queries over stored rows
        return jnp.pad(qa, ((0, 0), (0, 0), (0, lat_row.shape[-1] - width)))

    def expand_kv(lat_in):
        t = lat_in.shape[1]
        kv = linear(params["kv_up"], lat_in[..., :m.kv_lora])
        kv = kv.reshape(b, t, h, m.nope_dim + m.v_dim)
        k_nope, v = kv[..., :m.nope_dim], kv[..., m.nope_dim:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                lat_in[:, :, None, m.kv_lora:width].astype(k_nope.dtype),
                (b, t, h, m.rope_dim))], axis=-1)
        return k, v

    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = qk_dim ** -0.5
    if mode == "verify":
        raise NotImplementedError(
            "'verify' mode is not implemented for MLA attention")
    if mode in ("train", "prefill"):
        k, v = expand_kv(lat)
        out = _chunked_attention(qfull, k, v, scale=scale, causal=True,
                                 window=cfg.window,
                                 q_chunk=cfg.attn_q_chunk)
        if mode == "prefill":
            cache = dict(cache)
            cache["latent"] = jax.lax.dynamic_update_slice_in_dim(
                cache["latent"], lat_row.astype(cache["latent"].dtype), 0, 1)
    elif mode == "chunk":
        # partial-prefill continuation, mirroring the dense chunk path:
        # write the chunk's latent rows at the clock, re-expand the WHOLE
        # cache, and mask the unwritten suffix by absolute position
        # (unwritten rows expand to K/V that are masked before softmax, so
        # they contribute exact zeros); the expansion is recomputed per
        # chunk, which can reassociate vs the monolithic prefill gemm —
        # served under the "mla" agreement budget (measured exact)
        cache = dict(cache)
        cache["latent"] = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], lat_row.astype(cache["latent"].dtype), pos, 1)
        lat_all = shard_act(cache["latent"], ("batch", "seq_shard", None))
        k, v = expand_kv(lat_all.astype(x.dtype))
        out = _chunked_attention(qfull, k, v, scale=scale, causal=True,
                                 window=None, q_chunk=cfg.attn_q_chunk,
                                 row0=pos)
    elif block_tables is not None:   # paged decode: s == 1, pos is (B,)
        # the new latent row lands through the table (retired slots write
        # the trash block); attention runs absorbed over the latent pool,
        # which the gather kernel reads as both K and V
        bs_blk = cache["latent"].shape[1]
        rows = jnp.arange(b)
        phys = block_tables[rows, pos // bs_blk]
        cache = dict(cache)
        cache["latent"] = cache["latent"].at[phys, pos % bs_blk].set(
            lat_row[:, 0].astype(cache["latent"].dtype))
        from repro.kernels.paged_attention import paged_attention
        pool = cache["latent"][:, :, None]
        out = _mla_absorbed_decode(
            params, qfull, m, h, scale,
            lambda qa: paged_attention(wide(qa), pool, pool, block_tables,
                                       pos, scale=scale))
    else:                            # contiguous decode at clock ``pos``
        cache = dict(cache)
        cache["latent"] = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], lat_row.astype(cache["latent"].dtype), pos, 1)
        lat_all = shard_act(cache["latent"], ("batch", "seq_shard", None))
        t = lat_all.shape[1]
        mask = jnp.where(jnp.arange(t) <= pos, 0.0,
                         NEG_INF)[None, None, None, None, :]
        lat_all = lat_all.astype(x.dtype)
        if m.absorb:
            heads = lat_all[:, :, None]                 # one "KV head"
            out = _mla_absorbed_decode(
                params, qfull, m, h, scale,
                lambda qa: _grouped_attention(wide(qa)[:, None], heads,
                                              heads, mask, scale)[:, 0])
        else:
            k, v = expand_kv(lat_all)
            out = _grouped_attention(qfull, k, v, mask, scale)
    out = out.reshape(b, s, h * m.v_dim)
    return linear(params["wo"], out), cache


def _kv_up_weight(params, m: MLAConfig, h: int, dtype):
    """``kv_up`` as (kv_lora, H, nope + v), fp or quantized."""
    w = params["kv_up"]["w"]
    if hasattr(w, "dequantize"):                       # (out, in) codes
        w = w.dequantize(dtype).T
    return w.astype(dtype).reshape(m.kv_lora, h, m.nope_dim + m.v_dim)


def _mla_absorbed_decode(params, qfull, m: MLAConfig, h: int, scale,
                         attend):
    """Weight-absorbed MLA decode: attend in the latent space.

    scores[h,s] = (W_uk[h]ᵀ q_nope[h]) · n(c_s)  +  q_rope[h] · k_rope_s
    out[h]      = W_uv[h] @ Σ_s p[h,s] · n(c_s)

    ``attend(qa)`` takes the absorbed queries ``(B, H, L+R)`` against the
    latent rows ``n(c_s) ‖ k_rope_s`` (their first ``L`` lanes are the
    values) and returns ``(B, H, W)`` for rows ``W`` lanes wide, of which
    the first ``L`` are used. Per step this costs
    O(H·kv_lora·(nope+v)) for the two absorptions plus O(S·H·kv_lora) for
    attention — no cache re-expansion.
    """
    q_nope = qfull[:, 0, :, :m.nope_dim]           # (B,H,nope)
    q_rope = qfull[:, 0, :, m.nope_dim:]           # (B,H,rope)
    w_up = _kv_up_weight(params, m, h, qfull.dtype)
    w_uk = w_up[..., :m.nope_dim]                  # (kv_lora, H, nope)
    w_uv = w_up[..., m.nope_dim:]                  # (kv_lora, H, v)
    q_tilde = jnp.einsum("bhn,chn->bhc", q_nope, w_uk)
    attended = attend(jnp.concatenate([q_tilde, q_rope], -1))
    out = jnp.einsum("bhc,chv->bhv", attended[..., :m.kv_lora], w_uv)
    return out[:, None]                            # (B,1,H,v)


def latent_store_width(m: MLAConfig) -> int:
    """Lanes a cached latent row takes: ``kv_lora + rope_dim`` rounded up
    to whole 128-lane tiles (576 → 640)."""
    return -(-(m.kv_lora + m.rope_dim) // 128) * 128


def init_mla_cache(batch: int, max_len: int, cfg, dtype=jnp.bfloat16):
    return {"latent": jnp.zeros((batch, max_len,
                                 latent_store_width(cfg.mla)), dtype)}


def cross_attention(params, x, enc_out, *, cfg,
                    enc_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Decoder cross-attention over encoder output (full MHA)."""
    b, s, d = x.shape
    hd = cfg.head_dim
    t = enc_out.shape[1]
    q = linear(params["wq"], x).reshape(b, s, cfg.n_heads, hd)
    k = linear(params["wk"], enc_out).reshape(b, t, cfg.n_heads, hd)
    v = linear(params["wv"], enc_out).reshape(b, t, cfg.n_heads, hd)
    if enc_mask is not None:
        mask = jnp.where(enc_mask, 0.0, NEG_INF)[:, None, None, None, :]
        return linear(params["wo"],
                      _grouped_attention(q, k, v, mask, hd ** -0.5)
                      .reshape(b, s, cfg.n_heads * hd))
    out = _chunked_attention(q, k, v, scale=hd ** -0.5, causal=False,
                             q_chunk=cfg.attn_q_chunk)
    return linear(params["wo"], out.reshape(b, s, cfg.n_heads * hd))
