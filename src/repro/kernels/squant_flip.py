"""Pallas TPU kernel for the SQuant progressive flip (Sec. 3.4).

TPU adaptation of the paper's CUDA kernel (one thread-block per output
channel + warp top-k). There is no warp shuffle / data-dependent sort on the
TPU vector unit, so selection is re-thought as *rank-via-comparison*:

    rank_i = Σ_j [score_j > score_i] + Σ_{j<i} [score_j == score_i]
    flip_i = rank_i < k

a dense (G×G) fixed-shape comparison that lives entirely in VMEM and maps
onto the 8×128 VPU lanes. Two passes:

* ``squant_ek_kernel`` — grid (M/TM, N/G), block (TM, G): fused
  round (SQuant-E) + group flip (SQuant-K) + the Algorithm-4 candidate
  (index+value) for the C stage, written as lane ``j`` of (TM, NG)
  summary blocks that stay resident across the group axis.
* ``squant_c_kernel``  — grid (M/TM_C,), block (TM_C, NG): ranks groups by
  |candidate| and emits the per-group flip decision (SQuant-C). The ±1
  application is a cheap one-hot select done by the wrapper (no scatter —
  TPU-friendly).

Both are validated in interpret mode against ``kernels/ref.py`` (which
delegates to the vectorized core, itself bit-exact against the sequential
NumPy reference of Algorithms 1-4), and compiled on a TPU against the same
reference by ``chip_smoke.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ranks_desc_2d(score: jnp.ndarray) -> jnp.ndarray:
    """Stable descending rank along the last axis via pairwise comparison.

    score: (R, L) → int32 (R, L). Lower index wins ties (matches the stable
    argsort of the jnp reference).
    """
    r, l = score.shape
    s_i = score[:, :, None]                      # (R, L, 1) "self"
    s_j = score[:, None, :]                      # (R, 1, L) "other"
    ii = jax.lax.broadcasted_iota(jnp.int32, (r, l, l), 1)
    jj = jax.lax.broadcasted_iota(jnp.int32, (r, l, l), 2)
    beats = (s_j > s_i) | ((s_j == s_i) & (jj < ii))
    return jnp.sum(beats.astype(jnp.int32), axis=2)


def _flip_body(q, delta, qmax):
    """Shared E→K flip math on a (R, L) tile; returns updated (q, delta)."""
    e = jnp.sum(delta, axis=1, keepdims=True)
    k = jnp.round(jnp.abs(e)).astype(jnp.int32)
    tgt = q - jnp.sign(delta)
    in_range = (tgt >= -qmax) & (tgt <= qmax)
    eligible = (delta * e > 0) & in_range
    k = jnp.minimum(k, jnp.sum(eligible.astype(jnp.int32), axis=1,
                               keepdims=True))
    score = jnp.where(eligible, jnp.abs(delta), -1.0)
    flip = (_ranks_desc_2d(score) < k) & eligible
    sgn = jnp.sign(delta)
    q = q - jnp.where(flip, sgn, 0.0)
    delta = delta - jnp.where(flip, sgn, 0.0)
    return q, delta


def squant_ek_kernel(ws_ref, q_ref, e1_ref, cidx_ref, cval_ref, *,
                     qmax: float, enable_k: bool):
    """Fused SQuant-E (+K) + Algorithm-4 candidate for one (TM, G) block.

    ``ws_ref`` holds the already-scaled weights ``w / s``. The three
    per-group summaries live in (TM, NG) blocks that stay resident while
    the group axis ``j`` sweeps (innermost grid axis): group ``j`` writes
    lane ``j``, so every output block is lane-dense and spans all NG
    columns (the TPU tiling rule) instead of a (TM, 1) sliver.
    """
    ws = ws_ref[...]
    q = jnp.clip(jnp.round(ws), -qmax, qmax)
    delta = q - ws

    if enable_k:
        q, delta = _flip_body(q, delta, qmax)

    # Post-K group sum and the single C-stage candidate (Algorithm 4).
    e1 = jnp.sum(delta, axis=1, keepdims=True)          # (TM, 1)
    sgn1 = jnp.sign(e1)
    # e1 == 0 admits either sign; written with logical ops, not a
    # bool-valued select (Mosaic cannot lower a select between i1 vectors)
    match = ((sgn1 == 0.0) & (delta != 0.0)) | (delta * sgn1 > 0.0)
    tgt = q - jnp.sign(delta)
    match = match & (tgt >= -qmax) & (tgt <= qmax)
    cscore = jnp.where(match, jnp.abs(delta), -1.0)
    cmax = jnp.max(cscore, axis=1, keepdims=True)       # (TM, 1)
    l = cscore.shape[1]
    ii = jax.lax.broadcasted_iota(jnp.int32, cscore.shape, 1)
    first = jnp.min(jnp.where(cscore == cmax, ii, l), axis=1, keepdims=True)
    cand_val = jnp.sum(jnp.where(ii == first, delta, 0.0), axis=1,
                       keepdims=True)
    has = cmax > 0.0
    q_ref[...] = q.astype(jnp.int32)

    j = pl.program_id(1)
    lane = jax.lax.broadcasted_iota(jnp.int32, e1_ref.shape, 1)
    mine = lane == j
    e1_ref[...] = jnp.where(mine, e1, e1_ref[...])
    cidx_ref[...] = jnp.where(mine, jnp.where(has, first, -1), cidx_ref[...])
    cval_ref[...] = jnp.where(mine, jnp.where(has, cand_val, 0.0),
                              cval_ref[...])


def squant_c_kernel(e1_ref, cval_ref, gflip_ref):
    """SQuant-C decision on one (TM_C, NG) block of group summaries."""
    e1 = e1_ref[...]
    cval = cval_ref[...]
    e_row = jnp.sum(e1, axis=1, keepdims=True)
    k_c = jnp.round(jnp.abs(e_row)).astype(jnp.int32)
    elig = (cval * e_row > 0.0)                          # cval==0 → ineligible
    k_c = jnp.minimum(k_c, jnp.sum(elig.astype(jnp.int32), axis=1,
                                   keepdims=True))
    score = jnp.where(elig, jnp.abs(cval), -1.0)
    gflip = (_ranks_desc_2d(score) < k_c) & elig
    gflip_ref[...] = gflip.astype(jnp.int32)


def _pad_to(x, mult, axis, value=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=(
    "bits", "group_size", "enable_k", "enable_c", "tm", "interpret"))
def squant_pallas(w2d: jnp.ndarray, scale: jnp.ndarray, *, bits: int,
                  group_size: int, enable_k: bool = True,
                  enable_c: bool = True, tm: int = 8,
                  interpret: bool = False):
    """Full SQuant E(&K)(&C) via the two Pallas passes. Returns int8 codes.

    w2d: (M, N) float; scale: (M, 1). N is padded to a multiple of
    ``group_size``, M to a multiple of ``tm`` (zero rows/cols are inert:
    δ=0 elements are never flip-eligible and contribute nothing to sums).
    On a TPU ``group_size`` is a multiple of 128 (or the padded row) and
    ``tm`` a multiple of 8: the block tiling rule.
    """
    qmax = float(2 ** (bits - 1) - 1)
    m0, n0 = w2d.shape
    g = group_size
    # scale exactly as the jnp reference does (one XLA divide), so the
    # E-stage inputs of both implementations are the same bits
    ws = w2d.astype(jnp.float32) / scale.astype(jnp.float32).reshape(m0, 1)
    ws = _pad_to(_pad_to(ws, g, 1), tm, 0)
    m, n = ws.shape
    ng = n // g

    kern = functools.partial(squant_ek_kernel, qmax=qmax, enable_k=enable_k)
    summary = pl.BlockSpec((tm, ng), lambda i, j: (i, 0))
    q, e1, cidx, cval = pl.pallas_call(
        kern,
        grid=(m // tm, ng),
        in_specs=[pl.BlockSpec((tm, g), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((tm, g), lambda i, j: (i, j)),
                   summary, summary, summary],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.int32),
            jax.ShapeDtypeStruct((m, ng), jnp.float32),
            jax.ShapeDtypeStruct((m, ng), jnp.int32),
            jax.ShapeDtypeStruct((m, ng), jnp.float32),
        ],
        interpret=interpret,
    )(ws)

    if enable_c:
        # keep the (TM_C, NG, NG) comparison tensor under ~2 MiB of VMEM;
        # tm_c must divide the (tm-padded) m or the floor-divided grid
        # leaves the last m % tm_c rows of gflip unwritten
        tm_c = max(1, min(tm, (1 << 19) // max(ng * ng, 1)))
        while m % tm_c:
            tm_c -= 1
        gflip = pl.pallas_call(
            squant_c_kernel,
            grid=(m // tm_c,),
            in_specs=[
                pl.BlockSpec((tm_c, ng), lambda i: (i, 0)),
                pl.BlockSpec((tm_c, ng), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((tm_c, ng), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((m, ng), jnp.int32),
            interpret=interpret,
        )(e1, cval)
        # apply: one ±1 mutation per flipped group at the candidate position
        qg = q.reshape(m, ng, g)
        ii = jax.lax.broadcasted_iota(jnp.int32, qg.shape, 2)
        hit = (ii == cidx[..., None]) & (gflip[..., None] > 0)
        qg = qg - jnp.where(hit, jnp.sign(cval)[..., None], 0.0).astype(q.dtype)
        q = qg.reshape(m, n)

    return q[:m0, :n0].astype(jnp.int8)
