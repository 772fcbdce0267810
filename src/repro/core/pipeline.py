"""Model-level on-the-fly quantization driver.

Walks a parameter pytree, quantizes every matmul weight with the requested
data-free method, and returns (new_tree, report). This is the "on-the-fly
framework" of Sec. 3.4: no data, no back-prop, wall time recorded (Table 3's
protocol).

Execution modes:

* ``batched=True`` (default) — leaves are grouped into same-(2-D view shape,
  dtype, group) buckets; each bucket is stacked and quantized with ONE
  asynchronous dispatch (vmapped jnp core or a single flattened Pallas
  launch, see ``core.dispatch``), and the whole tree synchronizes with the
  device ONCE at the end. ``QuantReport`` carries the whole call's wall
  time and the buckets; where the host spends it (plan, stack, dispatch,
  wrap, sync) is in the ``quantize.*`` host spans of a profiler trace.
* ``batched=True, mesh=...`` — same bucketing, but each bucket's rows are
  partitioned over the mesh's ``mesh_axis`` under ``shard_map``: every device
  quantizes its own output-channel slab (SQuant is row-independent, so the
  partition is exact — codes/scales are bitwise identical to the unsharded
  path). Output ``QuantizedTensor`` codes+scales inherit the source param's
  sharding rules (``distributed.sharding.quantized_tensor_shardings``), and
  the report gains a per-device shard breakdown.
* ``batched=False`` — the legacy per-layer reference path: one quantization
  call and one ``block_until_ready`` per leaf. Kept as the bit-exactness
  oracle and the serial baseline for ``benchmarks/bench_time.py``.

``backend`` selects the kernel implementation for the batched path
(``"auto" | "ref" | "pallas" | "interpret"``, see ``core.dispatch.BACKENDS``);
the serial path always uses the jnp reference.

Conventions (shared with ``repro.models``):
* dense kernels are dict leaves named ``w`` with shape (in, out);
* expert kernels are named ``w`` with shape (experts, in, out);
* conv kernels (test CNNs) are named ``w_conv`` with shape (KH, KW, in, out);
* 1-D vectors (norm gains, biases, lerp vectors) are never quantized.

SQuant semantics: rows are OUTPUT channels, so (in, out) kernels are
transposed to (out, in) before quantization. The stored QuantizedTensor keeps
the (out, in) layout — the serving layer (`models.layers.linear` /
`kernels.dequant_matmul`) consumes it directly.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.dispatch import (bucket_impl, bucket_tile_rows,
                                 quantize_codes_batched,
                                 quantize_codes_sharded, resolve_backend,
                                 shard_rows)
from repro.quant.qtypes import (BucketReport, LayerReport, QuantReport,
                                ShardReport, from_codes)

METHODS = ("rtn", "squant", "squant_e", "squant_ek", "squant_ec")

# Module-level alias so tests can count device synchronizations: the batched
# path calls this exactly once per quantize_tree, the serial path once per
# quantized leaf.
_sync = jax.block_until_ready

# host spans on the profiler's clock (free while no trace is being taken):
# quantize.tree > quantize.plan, quantize.chunk > (quantize.stack,
# quantize.dispatch, quantize.wrap), quantize.sync; a chunk's metadata
# names its bucket, its layers and the kernel's tile height (0: no kernel)
_span = jax.profiler.TraceAnnotation


def is_quantizable(path: Tuple[str, ...], leaf: Any) -> bool:
    if not isinstance(leaf, (jnp.ndarray, jax.Array)):
        return False
    if "router" in path:       # MoE routers: tiny + precision-sensitive
        return False
    name = path[-1] if path else ""
    if name == "w" and leaf.ndim in (2, 3):
        return True
    if name == "w_conv" and leaf.ndim == 4:
        return True
    return False


# ---------------------------------------------------------------------------
# Leaf planning: every quantizable leaf maps to a 2-D (out, in)-major view
# ---------------------------------------------------------------------------

def _plan_leaf(leaf, method: str, group_size: Optional[int]
               ) -> Tuple[Tuple[int, int], Tuple[int, ...], Optional[int]]:
    """Return ``(w2d_shape, qt_shape, eff_group)`` for one kernel leaf,
    from its shape alone (:func:`_to_2d` builds the 2-D view itself).

    ``eff_group`` mirrors the clamping in ``core.squant.squant`` exactly
    (group >= row length degenerates to the whole-row FC path; conv kernels
    use K=KH*KW as the natural group) so batched results are bit-identical to
    the per-layer path.
    """
    if leaf.ndim == 2:                       # (in, out) -> (out, in)
        w2d_shape = qt_shape = (leaf.shape[1], leaf.shape[0])
    elif leaf.ndim == 3:                     # (E, in, out) -> (E*out, in)
        e, i, o = leaf.shape
        w2d_shape = qt_shape = (e * o, i)
    elif leaf.ndim == 4:                     # conv (KH,KW,in,out) -> (out, in*K)
        kh, kw, ci, co = leaf.shape
        k = kh * kw
        if method == "rtn":
            return (co, ci * k), (co, ci * k), None
        return (co, ci * k), (co, ci, k), (None if k == 1 else k)
    else:
        raise ValueError(f"unsupported kernel rank {leaf.ndim}")
    if method == "rtn":
        return w2d_shape, qt_shape, None
    n = w2d_shape[1]
    eff = None if (group_size is None or group_size >= n) else group_size
    return w2d_shape, qt_shape, eff


def _to_2d(leaf: jnp.ndarray) -> jnp.ndarray:
    """The (out, in)-major 2-D view :func:`_plan_leaf` describes."""
    if leaf.ndim == 2:
        return leaf.T
    if leaf.ndim == 3:
        e, i, o = leaf.shape
        return jnp.transpose(leaf, (0, 2, 1)).reshape(e * o, i)
    kh, kw, ci, co = leaf.shape
    return jnp.transpose(leaf, (3, 2, 0, 1)).reshape(co, ci * kh * kw)


@jax.jit
def _stack_2d(leaves) -> jnp.ndarray:
    """(B, M, N) stack of the leaves' 2-D views in ONE device op: a bucket
    chunk is transposed and stacked together, so no transposed copy of
    the whole tree ever exists beside the source params."""
    return jnp.stack([_to_2d(leaf) for leaf in leaves])


def _restore_dense(wq: jnp.ndarray, leaf_shape: Tuple[int, ...]
                   ) -> jnp.ndarray:
    """Fake-quant restore: (out, in)-major dequantized weights -> leaf layout."""
    if len(leaf_shape) == 2:
        return wq.T
    if len(leaf_shape) == 3:
        e, i, o = leaf_shape
        return jnp.transpose(wq.reshape(e, o, i), (0, 2, 1))
    kh, kw, ci, co = leaf_shape
    return jnp.transpose(wq.reshape(co, ci, kh, kw), (2, 3, 1, 0))


# ---------------------------------------------------------------------------
# Serial per-layer path (one dispatch + one device sync per leaf)
# ---------------------------------------------------------------------------

def _quantize_tree_serial(flat, treedef, pred, method, bits, group_size,
                          scale_method, dequantize):
    """Per-layer baseline: same dispatch helpers as the batched path, called
    with B=1 and synchronized after every leaf (the pre-batching protocol
    Table 3 timings were taken under)."""
    out_leaves = []
    reports: List[LayerReport] = []
    t_total = 0.0
    for keypath, leaf in flat:
        path = tuple(str(getattr(k, "key", getattr(k, "idx", str(k))))
                     for k in keypath)
        if not pred(path, leaf):
            out_leaves.append(leaf)
            continue
        t0 = time.perf_counter()
        _, qt_shape, eff = _plan_leaf(leaf, method, group_size)
        codes, scales = quantize_codes_batched(
            _to_2d(leaf)[None], method=method, bits=bits, group_size=eff,
            scale_method=scale_method, backend="ref")
        qt = from_codes(codes[0].reshape(qt_shape), scales[0], bits)
        _sync(qt.data)
        ms = (time.perf_counter() - t0) * 1e3
        t_total += ms
        reports.append(LayerReport("/".join(path), tuple(leaf.shape), ms,
                                   method, bits))
        if dequantize:
            out_leaves.append(_restore_dense(qt.dequantize(leaf.dtype),
                                             tuple(leaf.shape)))
        else:
            out_leaves.append(qt)
    tree = jax.tree_util.tree_unflatten(treedef, out_leaves)
    return tree, QuantReport(reports, t_total, method, bits, backend="ref")


# ---------------------------------------------------------------------------
# Batched path: bucket -> stack -> one dispatch per bucket -> one sync total
# ---------------------------------------------------------------------------

# Cap on the transient stacked-bucket buffer: buckets whose stack would
# exceed this many bytes are dispatched in chunks, bounding the kernel's
# working set by one chunk instead of the largest bucket. Still one device
# sync per tree. The compiled Pallas SQuant pass needs about 6.6x a bf16
# chunk's bytes of temporaries on a TPU (f32 scaled weights, int32 codes
# and C-stage copies: 7.1 GB for a 1 GiB chunk of 2048x2048 layers, from
# the v5e compiler's memory analysis). 256 MiB keeps that under 2 GB, so a
# live reload fits beside the serving tree on a 16 GB chip.
_MAX_STACK_BYTES = 256 << 20


def _dispatch_chunk(ws, method, bits, eff, scale_method, backend, mesh,
                    mesh_axis):
    """Codes and scales of one stacked bucket chunk, unsharded or with its
    rows partitioned over ``mesh_axis``."""
    if mesh is None:
        return quantize_codes_batched(
            ws, method=method, bits=bits, group_size=eff,
            scale_method=scale_method, backend=backend)
    return quantize_codes_sharded(
        ws, method=method, bits=bits, group_size=eff,
        scale_method=scale_method, backend=backend, mesh=mesh,
        mesh_axis=mesh_axis)


def _wrap_leaf(codes, scales, bits, leaf, path, dequantize, mesh):
    """One leaf's output: its ``QuantizedTensor``, or the dequantized
    weights in the leaf's layout (``dequantize``)."""
    qt = from_codes(codes, scales, bits)
    if dequantize:
        return _restore_dense(qt.dequantize(leaf.dtype), tuple(leaf.shape))
    if mesh is not None:
        # codes/scales inherit the source param's sharding rules
        from repro.distributed.sharding import quantized_tensor_shardings
        return qt.with_placement(*quantized_tensor_shardings(mesh, path, qt))
    return qt


def _quantize_tree_batched(flat, treedef, pred, method, bits, group_size,
                           scale_method, dequantize, backend, mesh,
                           mesh_axis):
    ndev = int(dict(mesh.shape)[mesh_axis]) if mesh is not None else 1
    shard_acc = [[0, 0] for _ in range(ndev)]   # per-device [rows, pad_rows]

    t_begin = time.perf_counter()
    out_leaves: List[Any] = [None] * len(flat)
    # bucket key -> list of (leaf index, path, leaf, qt_shape)
    buckets: Dict[Tuple, List] = {}
    with _span("quantize.plan"):
        for idx, (keypath, leaf) in enumerate(flat):
            path = tuple(str(getattr(k, "key", getattr(k, "idx", str(k))))
                         for k in keypath)
            if not pred(path, leaf):
                out_leaves[idx] = leaf
                continue
            w2d_shape, qt_shape, eff = _plan_leaf(leaf, method, group_size)
            key = (w2d_shape, str(leaf.dtype), eff)
            buckets.setdefault(key, []).append((idx, path, leaf, qt_shape))

    layer_reports: List[LayerReport] = []
    bucket_reports: List[BucketReport] = []
    quantized: List[Any] = []                 # everything the final sync waits on
    n_q = sum(len(v) for v in buckets.values())
    for key, all_entries in buckets.items():
        (m, n), dtype, eff = key[0], key[1], key[2]
        layer_bytes = m * n * jnp.dtype(dtype).itemsize
        chunk = max(1, min(len(all_entries), _MAX_STACK_BYTES // layer_bytes))
        impl = bucket_impl(method, backend, eff)
        for c0 in range(0, len(all_entries), chunk):
            entries = all_entries[c0:c0 + chunk]
            tag = f"({m},{n})x{len(entries)} {dtype} g{eff}"
            tb0 = time.perf_counter()
            tile = bucket_tile_rows(impl, len(entries) * m, ndev)
            with _span("quantize.chunk", bucket=tag, layers=len(entries),
                       tile_rows=tile):
                with _span("quantize.stack"):
                    ws = _stack_2d(tuple(e[2] for e in entries))  # (B,M,N)
                with _span("quantize.dispatch"):
                    codes, scales = _dispatch_chunk(
                        ws, method, bits, eff, scale_method, backend, mesh,
                        mesh_axis)
                if mesh is not None:
                    for d, (r, p) in enumerate(
                            shard_rows(len(entries) * m, ndev)):
                        shard_acc[d][0] += r
                        shard_acc[d][1] += p
                with _span("quantize.wrap"):
                    for bi, (idx, path, leaf, qt_shape) in enumerate(entries):
                        out = _wrap_leaf(codes[bi].reshape(qt_shape),
                                         scales[bi], bits, leaf, path,
                                         dequantize, mesh)
                        out_leaves[idx] = out
                        quantized.append(out)
            bucket_ms = (time.perf_counter() - tb0) * 1e3
            bucket_reports.append(BucketReport(tag, len(entries), impl))
            for idx, path, leaf, _ in entries:
                layer_reports.append(LayerReport("/".join(path),
                                                 tuple(leaf.shape),
                                                 bucket_ms / len(entries),
                                                 method, bits, bucket=tag))

    t_sync0 = time.perf_counter()
    with _span("quantize.sync"):
        _sync(quantized)                      # the ONE device sync
    sync_ms = (time.perf_counter() - t_sync0) * 1e3
    # fold the sync into per-layer numbers so Σ layer.millis ≈ total
    for lr in layer_reports:
        lr.millis += sync_ms / max(n_q, 1)

    tree = jax.tree_util.tree_unflatten(treedef, out_leaves)
    total_ms = (time.perf_counter() - t_begin) * 1e3
    shards = [ShardReport(d, r, p) for d, (r, p) in enumerate(shard_acc)] \
        if mesh is not None else []
    return tree, QuantReport(layer_reports, total_ms, method, bits,
                             backend=backend, buckets=bucket_reports,
                             mesh_axis=mesh_axis if mesh is not None else "",
                             mesh_size=ndev, shards=shards)


def quantize_tree(params: Any, method: str = "squant", bits: int = 4,
                  group_size: Optional[int] = 128, scale_method: str = "max",
                  predicate: Optional[Callable] = None,
                  dequantize: bool = False, backend: str = "auto",
                  batched: bool = True, mesh=None,
                  mesh_axis: str = "data") -> Tuple[Any, QuantReport]:
    """Quantize all matmul weights in a param tree.

    dequantize=True returns float weights (fake-quant — for accuracy evals on
    models whose forward pass expects dense arrays); otherwise leaves become
    QuantizedTensor (real serving format).

    backend: kernel implementation for the batched path — one of
    ``core.dispatch.BACKENDS`` (``"auto"`` resolves TPU→pallas, CPU→ref).
    batched=False falls back to the legacy per-layer loop (one dispatch and
    one device sync per leaf); it ignores ``backend`` and always runs the jnp
    reference.

    mesh: a ``jax.sharding.Mesh`` with a ``mesh_axis`` axis (see
    ``launch.mesh.make_quantize_mesh``) shards every bucket's rows across
    that axis under shard_map — exact (row-independent objective), results
    bitwise identical to ``mesh=None``. Sharded runs require ``batched=True``
    and report a per-device breakdown in ``QuantReport.shards``.
    """
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; options {METHODS}")
    if mesh is not None and mesh_axis not in dict(mesh.shape):
        raise ValueError(f"mesh has no {mesh_axis!r} axis; axes: "
                         f"{tuple(dict(mesh.shape))}")
    backend = resolve_backend(backend)
    pred = predicate or is_quantizable
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    if not batched and mesh is not None:
        raise ValueError("mesh= requires batched=True (the serial "
                         "baseline is single-device by definition)")
    with _span("quantize.tree") as span:
        if batched:
            tree, report = _quantize_tree_batched(
                flat, treedef, pred, method, bits, group_size, scale_method,
                dequantize, backend, mesh, mesh_axis)
        else:
            tree, report = _quantize_tree_serial(
                flat, treedef, pred, method, bits, group_size, scale_method,
                dequantize)
        span.set_metadata(leaves=len(report.layers),
                          buckets=len(report.buckets))
    return tree, report
