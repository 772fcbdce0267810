"""Plain SQuant (E, then K per group, then C per row) over a few rows.

Imports nothing of the program: the paper's Algorithms 1-4 as the
repository's loop transcription states them, written over a batch of rows
with NumPy (every row is quantized on its own, so any sample of rows can
be checked). Scales are per row, ``max|w| / qmax`` in float32, and the
scaled weights ``w / s`` are float32 as stored; sums run in float64.

* E: round to nearest (ties to even), clip to ``[-qmax, qmax]``.
* K, per group of ``group_size``: with ``e`` the group's sum of
  ``delta = q - w/s``, flip the ``round(|e|)`` elements of largest
  ``|delta|`` whose delta has the sign of ``e`` and whose flipped code
  stays in range (lower index first among equals).
* C, per row: each group offers the in-range element of largest
  ``|delta|`` with the sign of its (post-K) group sum (any nonzero delta
  when that sum is 0); with ``e`` the row sum, flip the ``round(|e|)``
  offers of largest ``|delta|`` whose delta has the sign of ``e``. With
  no groups (``group_size`` None) K is skipped and C flips elements of
  the whole row the way K flips a group's.
* Codes of 4 bits or fewer are packed two to a byte, the even column in
  the low nibble.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


def scales(w: np.ndarray, bits: int) -> np.ndarray:
    qmax = np.float32(2 ** (bits - 1) - 1)
    amax = np.max(np.abs(w.astype(np.float32)), axis=-1, keepdims=True)
    return (np.maximum(amax, np.float32(1e-12)) / qmax).astype(np.float32)


def _topk_mask(score: np.ndarray, k: np.ndarray) -> np.ndarray:
    """Per leading index, mark the ``k`` largest entries of the last axis
    (stable: lower index first among equals)."""
    order = np.argsort(-score, axis=-1, kind="stable")
    ranks = np.empty_like(order)
    np.put_along_axis(ranks, order,
                      np.broadcast_to(np.arange(score.shape[-1]),
                                      score.shape), axis=-1)
    return ranks < k[..., None]


def _flip_set(delta, q, qmax, sel):
    """Algorithm 2 on the last axis of ``delta`` (restricted to ``sel``):
    returns the mask of elements to flip."""
    e = delta.sum(-1)
    p = np.where(sel & (e[..., None] * delta > 0), delta, 0.0)
    ok = np.abs(q - np.sign(delta)) <= qmax
    p = np.where(ok, p, 0.0)
    k = np.minimum(np.round(np.abs(e)), np.count_nonzero(p, -1)).astype(int)
    return _topk_mask(np.abs(p), k) & (p != 0)


def codes(w: np.ndarray, bits: int, group_size: Optional[int],
          s: Optional[np.ndarray] = None) -> Tuple[np.ndarray, np.ndarray]:
    """(codes int8 (R, N), scales f32 (R, 1)) for rows ``w`` (R, N), with
    the scales given or their own."""
    qmax = 2 ** (bits - 1) - 1
    s = scales(w, bits) if s is None else np.asarray(s, np.float32)
    ws = (w.astype(np.float32) / s).astype(np.float64)
    r, n = ws.shape
    q = np.clip(np.round(ws), -qmax, qmax)
    delta = q - ws
    ones = np.ones_like(delta, bool)
    if group_size is None:
        f = _flip_set(delta, q, qmax, ones)
        sg = np.sign(delta)
        q = q - np.where(f, sg, 0.0)
        return q.astype(np.int8), s
    g = group_size
    ng = -(-n // g)
    pad = ng * g - n
    qg = np.pad(q, ((0, 0), (0, pad))).reshape(r, ng, g)
    dg = np.pad(delta, ((0, 0), (0, pad))).reshape(r, ng, g)
    # K: each group on its own
    f = _flip_set(dg, qg, qmax, np.ones_like(dg, bool))
    sg = np.sign(dg)
    qg = qg - np.where(f, sg, 0.0)
    dg = dg - np.where(f, sg, 0.0)
    # C: one offer per group, then Algorithm 2 over the offers of a row
    e1 = dg.sum(-1, keepdims=True)
    s1 = np.sign(e1)
    match = np.where(s1 == 0, dg != 0, dg * s1 > 0)
    match &= np.abs(qg - np.sign(dg)) <= qmax
    has = match.any(-1)                                        # (r, ng)
    j = np.argmax(np.where(match, np.abs(dg), -1.0), axis=-1)  # first max
    cval = np.take_along_axis(dg, j[..., None], -1)[..., 0]
    e_row = dg.sum((1, 2))[:, None]
    elig = has & (cval * e_row > 0)
    k_c = np.minimum(np.round(np.abs(e_row[:, 0])), elig.sum(-1)).astype(int)
    pick = _topk_mask(np.where(elig, np.abs(cval), -1.0), k_c) & elig
    hit = (np.arange(g)[None, None, :] == j[..., None]) & pick[..., None]
    qg = qg - np.where(hit, np.sign(cval)[..., None], 0.0)
    return qg.reshape(r, ng * g)[:, :n].astype(np.int8), s


def pack(c: np.ndarray, bits: int) -> np.ndarray:
    """Codes as stored: int8 for more than 4 bits, else two per byte."""
    if bits > 4:
        return c.astype(np.int8)
    lo = c[:, 0::2].astype(np.int16) & 0x0F
    hi = (c[:, 1::2].astype(np.int16) & 0x0F) << 4
    return (hi | lo).astype(np.uint8).view(np.int8)
