"""Pallas TPU kernel for the SQuant progressive flip (Sec. 3.4).

TPU adaptation of the paper's CUDA kernel (one thread-block per output
channel + warp top-k). There is no warp shuffle / data-dependent sort on the
TPU vector unit, so the k flips of a row are chosen by *threshold
selection* (:func:`_select_top_k`): eligible scores are positive f32, whose
int32 bit patterns order like their values, so a 31-round bisection on the
bit pattern finds each row's k-th largest score ``t``; every lane above
``t`` flips, and so do the first ``k - #(> t)`` lanes equal to ``t`` (lower
index wins ties, as the stable argsort of the jnp reference). The per-row
counts and the tie prefix count are 0/1 masks times an all-ones or a
strictly triangular matrix on the MXU (bf16 in, f32 out: exact). Two passes:

* ``squant_ek_kernel`` — grid (M/TM, N/G), block (TM, G): fused
  round (SQuant-E) + group flip (SQuant-K) + the Algorithm-4 candidate
  (index+value) for the C stage, written as lane ``j`` of (TM, NG)
  summary blocks that stay resident across the group axis.
* ``squant_c_kernel``  — grid (M/TM,), block (TM, NG): selects groups by
  |candidate| and emits the per-group flip decision (SQuant-C). The ±1
  application is a cheap one-hot select done by the wrapper (no scatter —
  TPU-friendly).

TM is :func:`tile_rows` of the row count: tall tiles (up to 512 rows) for
real matrices, 8 for a handful of rows.

Both are validated in interpret mode against ``kernels/ref.py`` (which
delegates to the vectorized core, itself bit-exact against the sequential
NumPy reference of Algorithms 1-4), and compiled on a TPU against the same
reference by ``chip_smoke.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def tile_rows(rows: int) -> int:
    """Tile height for ``rows`` rows: the tallest of 512, 256, ..., 8 that
    the row count (padded to a multiple of 8) reaches. Rows are then padded
    to a multiple of it; zero rows are inert."""
    padded = -(-max(rows, 1) // 8) * 8
    return min(512, 1 << (padded.bit_length() - 1))


def _row_count(mask: jnp.ndarray, mat: jnp.ndarray) -> jnp.ndarray:
    """``mask @ mat`` for a 0/1 mask (R, L) and a 0/1 matrix (L, L) on the
    MXU: bf16 operands, f32 accumulation, exact for counts up to L."""
    return jnp.dot(mask.astype(jnp.bfloat16), mat,
                   preferred_element_type=jnp.float32)


def _select_top_k(score: jnp.ndarray, eligible: jnp.ndarray,
                  k: jnp.ndarray) -> jnp.ndarray:
    """Mask of the ``k`` largest eligible scores of each row, lower index
    first among ties: exactly ``stable_rank_desc(score) < k`` restricted to
    eligible lanes.

    score: (R, L) f32, positive where ``eligible``; k: (R, 1) int32, at most
    the row's eligible count. Returns a bool (R, L) mask.
    """
    r, l = score.shape
    # positive f32 order like their int32 bit patterns; ineligible → -1
    bits = jnp.where(eligible, jax.lax.bitcast_convert_type(score, jnp.int32),
                     -1)
    row = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    ones = jnp.ones((l, l), jnp.bfloat16)
    before = (row < col).astype(jnp.bfloat16)     # strictly upper triangular
    kf = k.astype(jnp.float32)
    # t: the largest bit pattern with at least k eligible scores at or above
    # it, i.e. the row's k-th largest (every lane of a row holds the same t)
    t = jnp.zeros((r, l), jnp.int32)
    for b in range(30, -1, -1):
        cand = t | (1 << b)
        t = jnp.where(_row_count(bits >= cand, ones) >= kf, cand, t)
    above = bits > t
    tie = bits == t
    need = kf - _row_count(above, ones)           # ties to take, ≥ 1 if k > 0
    take_tie = tie & (_row_count(tie, before) < need)
    return (above | take_tie) & (k > 0)


def _flip_body(q, delta, qmax):
    """Shared E→K flip math on a (R, L) tile; returns updated (q, delta)."""
    e = jnp.sum(delta, axis=1, keepdims=True)
    k = jnp.round(jnp.abs(e)).astype(jnp.int32)
    tgt = q - jnp.sign(delta)
    in_range = (tgt >= -qmax) & (tgt <= qmax)
    eligible = (delta * e > 0) & in_range
    k = jnp.minimum(k, jnp.sum(eligible.astype(jnp.int32), axis=1,
                               keepdims=True))
    flip = _select_top_k(jnp.abs(delta), eligible, k)
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
    """SQuant-C decision on one (TM, NG) block of group summaries."""
    e1 = e1_ref[...]
    cval = cval_ref[...]
    e_row = jnp.sum(e1, axis=1, keepdims=True)
    k_c = jnp.round(jnp.abs(e_row)).astype(jnp.int32)
    elig = (cval * e_row > 0.0)                          # cval==0 → ineligible
    k_c = jnp.minimum(k_c, jnp.sum(elig.astype(jnp.int32), axis=1,
                                   keepdims=True))
    gflip = _select_top_k(jnp.abs(cval), elig, k_c)
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
                  enable_c: bool = True, tm: Optional[int] = None,
                  interpret: bool = False):
    """Full SQuant E(&K)(&C) via the two Pallas passes. Returns int8 codes.

    w2d: (M, N) float; scale: (M, 1). N is padded to a multiple of
    ``group_size``, M to a multiple of the tile height ``tm`` (zero
    rows/cols are inert: δ=0 elements are never flip-eligible and
    contribute nothing to sums). ``tm`` defaults to :func:`tile_rows` of M;
    both passes use it. On a TPU ``group_size`` is a multiple of 128 (or
    the padded row) and ``tm`` a multiple of 8: the block tiling rule.
    """
    qmax = float(2 ** (bits - 1) - 1)
    m0, n0 = w2d.shape
    g = group_size
    tm = tile_rows(m0) if tm is None else tm
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
        gflip = pl.pallas_call(
            squant_c_kernel,
            grid=(m // tm,),
            in_specs=[
                pl.BlockSpec((tm, ng), lambda i: (i, 0)),
                pl.BlockSpec((tm, ng), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((tm, ng), lambda i: (i, 0)),
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
