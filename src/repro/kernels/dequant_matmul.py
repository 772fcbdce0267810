"""Pallas TPU kernel: quantized-weight matmul (the SQuant serving hot spot).

Computes ``y = x @ dequant(Wq).T`` where ``Wq`` holds int8 codes (or int4
packed two-per-byte) with per-channel or per-group scales.

TPU mapping:
* grid (B/TB, M/TM, N/TK). Scales are stored transposed, lane-dense
  ``(1 | NG, M)``: with per-group scales TK is one group, so one K tile
  sees exactly one scale per output row (a tile-constant multiply after
  the MXU dot); per-channel scales multiply the accumulator once, at the
  store.
* codes are upcast to the activation dtype *inside VMEM* (the HBM traffic is
  the int8/int4 bytes — this is the memory-roofline win quantization buys).
* f32 accumulation in a VMEM scratch across the K grid dimension (TPU grids
  iterate the last axis innermost, so the revisiting-accumulator pattern is
  safe).
* int4: a (TM, TK/2) packed block is split into its low (even-column) and
  high (odd-column) nibble planes with int32 shifts, and each plane meets
  the matching even/odd half of the activations — two dots, no gather and
  no lane interleave. TK/2 is a multiple of 128 bytes on the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _split_nibbles(packed: jnp.ndarray):
    """(R, C) int8 → ``(even, odd)`` int32 codes, each (R, C): byte ``i``
    holds code ``2i`` in its low nibble and ``2i+1`` in its high nibble
    (little-nibble-first, matches qtypes). Shifts run on int32, which the
    TPU vector unit has natively; arithmetic right shifts sign-extend."""
    p = packed.astype(jnp.int32)
    return (p << 28) >> 28, (p << 24) >> 28


def _dot_t(x, w):
    """x (TB, K) · w (TM, K)ᵀ on the MXU, f32 accumulation."""
    return jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dequant_matmul_kernel(*refs, n_tiles: int, packed: bool,
                           per_group: bool):
    if packed:
        xe_ref, xo_ref, w_ref, s_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, s_ref, o_ref, acc_ref = refs
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if packed:
        # the activations arrive split into even/odd columns, so the two
        # nibble planes multiply them directly: no lane interleave
        xe = xe_ref[...]
        even, odd = _split_nibbles(w_ref[...])
        part = _dot_t(xe, even.astype(jnp.float32).astype(xe.dtype)) + \
            _dot_t(xo_ref[...], odd.astype(jnp.float32).astype(xe.dtype))
    else:
        x = x_ref[...]
        part = _dot_t(x, w_ref[...].astype(jnp.float32).astype(x.dtype))
    if per_group:
        # this K tile is exactly group j: its (1, TM) lane-dense scale row
        part = part * s_ref[pl.ds(j, 1), :]
    acc_ref[...] += part

    @pl.when(j == n_tiles - 1)
    def _store():
        acc = acc_ref[...]
        if not per_group:
            acc = acc * s_ref[...]                       # (1, TM) per channel
        o_ref[...] = acc.astype(o_ref.dtype)


_TK = 512    # per-channel K tile: 512 codes; VMEM stays far under budget


def _k_tile(n: int, packed: bool) -> int:
    """Largest K tile ≤ ``_TK`` that divides ``n`` and keeps the code block
    lane-dense (a multiple of 128 bytes), else the whole row."""
    lane = 256 if packed else 128
    t = (min(_TK, n) // lane) * lane
    while t >= lane:
        if n % t == 0:
            return t
        t -= lane
    return n


@functools.partial(jax.jit, static_argnames=(
    "bits", "tb", "tm", "interpret", "out_dtype"))
def dequant_matmul_pallas(x: jnp.ndarray, codes: jnp.ndarray,
                          scale: jnp.ndarray, *, bits: int,
                          tb: int = 128, tm: int = 128,
                          interpret: bool = False,
                          out_dtype=None):
    """y[B, M] = x[B, N] @ (codes[M, N] * scale).T

    ``codes``: int8; when bits<=4 they are packed (M, N/2) two-per-byte.
    ``scale``: (M, 1) per-channel or (M, NG) per-group f32 — NG equal
    groups of N/NG columns.

    Tiles: ``tb`` must divide B (``ops.dequant_matmul`` pads B); M may
    leave a partial last tile (its out-of-range rows are never written).
    Per-group scales fix the K tile to one group; per-channel scales use
    a K tile of up to 512 codes and apply the scale once, at the store. On
    a TPU each block obeys the tiling rule: ``tb`` a multiple of the
    activation's sublane count, ``tm`` and the K tile (in bytes of
    codes) multiples of 128, or the whole dimension.
    """
    b, n = x.shape
    packed = bits <= 4
    m = codes.shape[0]
    n_codes = codes.shape[1] * (2 if packed else 1)
    if n_codes != n:
        raise ValueError(f"x has N={n} but codes unpack to {n_codes}")
    scale_t = scale.astype(jnp.float32).reshape(m, -1).T     # (1|NG, M)
    per_group = scale_t.shape[0] > 1
    if per_group:
        if n % scale_t.shape[0]:
            raise ValueError(f"N={n} does not split into "
                             f"{scale_t.shape[0]} equal scale groups")
        k_tile = n // scale_t.shape[0]
    else:
        k_tile = _k_tile(n, packed)
    out_dtype = out_dtype or x.dtype

    tb = min(tb, b)
    tm = min(tm, m)
    if b % tb:
        raise ValueError(f"B={b} must divide the batch tile {tb}")
    n_tiles = n // k_tile
    wt = k_tile // 2 if packed else k_tile
    xs = [x[:, 0::2], x[:, 1::2]] if packed else [x]
    x_specs = [pl.BlockSpec((tb, wt), lambda i, k, j: (i, j))] * len(xs)

    kern = functools.partial(_dequant_matmul_kernel, n_tiles=n_tiles,
                             packed=packed, per_group=per_group)
    return pl.pallas_call(
        kern,
        grid=(b // tb, pl.cdiv(m, tm), n_tiles),
        in_specs=x_specs + [
            pl.BlockSpec((tm, wt), lambda i, k, j: (k, j)),
            pl.BlockSpec((scale_t.shape[0], tm), lambda i, k, j: (0, k)),
        ],
        out_specs=pl.BlockSpec((tb, tm), lambda i, k, j: (i, k)),
        out_shape=jax.ShapeDtypeStruct((b, m), out_dtype),
        scratch_shapes=[pltpu.VMEM((tb, tm), jnp.float32)],
        interpret=interpret,
    )(*xs, codes, scale_t)
