"""Backend dispatch for the batched quantization pipeline.

``quantize_tree`` groups same-(shape, dtype) leaves into buckets; this module
turns one stacked bucket ``(B, M, N)`` into int8 codes + per-row scales with a
fixed, small number of asynchronous dispatches — no host sync. The
``backend`` string is threaded down to ``kernels/ops.squant_flip_batched``:

* ``"ref"``        — vmapped jnp core (``core.squant.squant_codes``); the
                     production path on CPU.
* ``"pallas"``     — compiled Pallas TPU kernel, one launch per bucket (the
                     batch is flattened into rows — SQuant is row-independent,
                     so ``(B, M, N) → (B*M, N)`` is exact, not approximate).
* ``"interpret"``  — same kernel body executed by the Pallas interpreter
                     (CPU validation of the TPU path).
* ``"auto"``       — TPU→pallas, anything else→ref.

Scales are computed by ONE jitted function regardless of backend, so flip
decisions (which compare ``w/s`` against the integer grid) are bitwise
comparable across backends. RTN has no custom kernel (it is a pure
elementwise round); it runs as one jitted vmapped op regardless of backend.

The serial per-layer path in ``core.pipeline`` calls these same helpers with
``B=1``, which makes batched-vs-serial bit-exactness hold by construction
while the batched path still exercises the stack/vmap equivalence.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import compat
from repro.kernels import ops
from repro.kernels.squant_flip import tile_rows
from repro.quant.qtypes import qmax_for_bits
from repro.quant.scales import compute_scale

BACKENDS = ("auto", "ref", "pallas", "interpret")   # == kernels.ops.IMPLS

_METHOD_FLAGS = {
    "squant":    (True, True),
    "squant_e":  (False, False),
    "squant_ek": (True, False),
    "squant_ec": (False, True),
}


def resolve_backend(backend: str) -> str:
    """Validate and resolve ``"auto"`` to a concrete backend."""
    return ops.resolve(backend)


def bucket_impl(method: str, backend: str, group_size: Optional[int]) -> str:
    """The implementation one bucket runs on (``QuantReport`` records it):
    ``ops.squant_impl`` for the SQuant methods, ``"ref"`` for RTN (a plain
    jitted round, no kernel). ``backend`` is already resolved."""
    if method == "rtn":
        return "ref"
    enable_k, enable_c = _METHOD_FLAGS[method]
    return ops.squant_impl(backend, group_size, enable_k, enable_c)


def bucket_tile_rows(impl: str, rows: int, ndev: int = 1) -> int:
    """Tile height of the ``squant_flip`` kernel for one dispatch of
    ``rows`` stacked rows (each device's slab where ``ndev`` devices share
    them); 0 where the bucket runs no kernel (``impl`` is ``"ref"``)."""
    if impl == "ref":
        return 0
    return tile_rows(sum(shard_rows(rows, ndev)[0]))


@functools.lru_cache(maxsize=None)
def _scales_fn(bits: int, scale_method: str):
    """jit(vmap(compute_scale)): the single scale source for all backends.

    Scales are computed in f32 whatever the weight dtype (they are stored
    as f32). A bf16 scale would be rounded where it is returned, but XLA
    may skip that rounding where it fuses the scale into the ``w / s`` of
    the same program (allowed excess precision) — the sharded path does,
    the unsharded one does not — and codes would then depend on the
    program they were computed in."""
    return jax.jit(jax.vmap(
        lambda w2d: compute_scale(w2d.astype(jnp.float32), bits,
                                  scale_method)))


@functools.lru_cache(maxsize=None)
def _rtn_fn(bits: int):
    qmax = qmax_for_bits(bits)

    def one(w2d, scale):
        return jnp.clip(jnp.round(w2d / scale), -qmax, qmax).astype(jnp.int8)
    return jax.jit(jax.vmap(one))


def quantize_codes_batched(ws: jnp.ndarray, *, method: str, bits: int,
                           group_size: Optional[int], scale_method: str = "max",
                           backend: str = "ref"
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize one stacked bucket.

    Args:
      ws: (B, M, N) stack of same-shape row-major weight matrices.
      group_size: effective kernel/group size for this bucket (None → whole
        row, the paper's FC path), already clamped by the caller.

    Returns ``(codes int8 (B, M, N), scales (B, M, 1))``. Everything is
    dispatched asynchronously; the caller owns the single end-of-pipeline
    sync.
    """
    scales = _scales_fn(bits, scale_method)(ws)
    if method == "rtn":
        codes = _rtn_fn(bits)(ws, scales)
    else:
        enable_k, enable_c = _METHOD_FLAGS[method]
        codes = ops.squant_flip_batched(
            ws, scales, bits=bits, group_size=group_size,
            enable_k=enable_k, enable_c=enable_c, use_pallas=backend)
    return codes, scales


# ---------------------------------------------------------------------------
# Sharded bucket dispatch (multi-device row partitioning)
# ---------------------------------------------------------------------------
# SQuant's flip objective is row-independent: every stage (E rounding, K
# group flips, C channel flips) and the scale computation operate within a
# single output-channel row. Partitioning the stacked bucket's B*M rows
# across a mesh axis is therefore EXACT — each device runs the same jitted
# helpers (`quantize_codes_batched` with B=1) on its row slab, so sharded
# codes/scales are bitwise identical to the unsharded batched path by
# construction.

@functools.lru_cache(maxsize=None)
def _sharded_fn(mesh, mesh_axis: str, method: str, bits: int,
                group_size: Optional[int], scale_method: str, backend: str):
    """jit(shard_map(...)) cached per (mesh, static config); shapes are
    handled by jit retracing."""

    def slab(local):                      # local: (rows/ndev, N) row slab
        codes, scales = quantize_codes_batched(
            local[None], method=method, bits=bits, group_size=group_size,
            scale_method=scale_method, backend=backend)
        return codes[0], scales[0]

    spec = P(mesh_axis, None)
    return jax.jit(compat.shard_map(
        slab, mesh, in_specs=spec, out_specs=(spec, spec),
        manual_axes={mesh_axis}))


def quantize_codes_sharded(ws: jnp.ndarray, *, method: str, bits: int,
                           group_size: Optional[int],
                           scale_method: str = "max", backend: str = "ref",
                           mesh, mesh_axis: str = "data"
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize one stacked bucket with its rows partitioned over
    ``mesh_axis`` of ``mesh``.

    The (B, M, N) stack is flattened to (B*M, N) rows, zero-padded so the
    axis size divides the row count, and dispatched under ``shard_map`` —
    each device quantizes its own slab with the same backend helpers the
    single-device path uses. Padding rows quantize to code 0 and are sliced
    off before the un-flatten. Results are bit-identical to
    :func:`quantize_codes_batched`.
    """
    sizes = dict(mesh.shape)
    if mesh_axis not in sizes:
        raise ValueError(f"mesh has no {mesh_axis!r} axis; axes: "
                         f"{tuple(sizes)}")
    b, m, n = ws.shape
    ndev = int(sizes[mesh_axis])
    rows = b * m
    # shard_rows is the single owner of the partition scheme: the pad here
    # and the QuantReport accounting both derive from it.
    pad = sum(p for _, p in shard_rows(rows, ndev))
    flat = ws.reshape(rows, n)
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    codes, scales = _sharded_fn(mesh, mesh_axis, method, bits, group_size,
                                scale_method, backend)(flat)
    return (codes[:rows].reshape(b, m, n),
            scales[:rows].reshape(b, m, 1))


def shard_rows(total_rows: int, ndev: int):
    """Per-device (rows, pad_rows) for one sharded dispatch — the partition
    scheme ``quantize_codes_sharded`` implements (contiguous equal slabs,
    zero rows padding the tail devices)."""
    pad = (-total_rows) % ndev
    per = (total_rows + pad) // ndev
    out = []
    for d in range(ndev):
        real = max(0, min(per, total_rows - d * per))
        out.append((real, per - real))
    return out
