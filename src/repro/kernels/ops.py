"""Jitted public wrappers for the Pallas kernels.

Dispatch policy (:func:`resolve`, shared by every kernel module):
* On TPU backends ``"auto"`` runs the compiled Pallas kernels.
* Elsewhere ``"auto"`` runs the pure-jnp reference; the CPU tests force
  ``use_pallas='interpret'`` to execute the kernel bodies (interpret mode
  is too slow for production-size tensors).
* An explicit ``"pallas"`` is always the compiled kernel: if it cannot
  lower, the error surfaces; nothing falls back to the reference.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.dequant_matmul import dequant_matmul_pallas
from repro.kernels.squant_flip import squant_pallas
from repro.quant.qtypes import QuantizedTensor


IMPLS = ("auto", "ref", "pallas", "interpret")


def resolve(use_pallas: str) -> str:
    """The one kernel-dispatch rule: ``"auto"`` is the compiled kernel on a
    TPU backend and the jnp reference anywhere else; an explicit choice is
    honoured as given (``"pallas"`` that cannot lower raises, it never
    falls back to the reference)."""
    if use_pallas not in IMPLS:
        raise ValueError(f"unknown kernel backend {use_pallas!r}; "
                         f"options {IMPLS}")
    if use_pallas == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return use_pallas


def squant_impl(use_pallas: str, group_size: Optional[int],
                enable_k: bool = True, enable_c: bool = True) -> str:
    """The implementation a SQuant call runs on: :func:`resolve`, except
    that whole-row groups (``group_size=None``, the paper's FC path) and
    the E&C-without-K ablation have no kernel specialization and run the
    jnp reference by design, whatever was asked for. ``QuantReport``
    records this per bucket so such buckets are visible, not hidden."""
    impl = resolve(use_pallas)
    if group_size is None or (enable_c and not enable_k):
        return "ref"
    return impl


def squant_flip(w2d: jnp.ndarray, scale: jnp.ndarray, *, bits: int,
                group_size: int, enable_k: bool = True, enable_c: bool = True,
                use_pallas: str = "auto", tm: Optional[int] = None
                ) -> jnp.ndarray:
    """SQuant codes for an (M, N) matrix with per-channel scales (M, 1).

    The Pallas path implements the standard E, E&K and E&K&C configurations;
    the E&C-without-K ablation (row-level flip) is reference-only. ``tm``
    overrides the kernel's tile height, which is otherwise derived from M
    (``squant_flip.tile_rows``).
    """
    impl = squant_impl(use_pallas, group_size, enable_k, enable_c)
    if impl == "ref":
        return _ref.squant_ref(w2d, scale, bits=bits, group_size=group_size,
                               enable_k=enable_k, enable_c=enable_c)
    return squant_pallas(w2d, scale, bits=bits, group_size=group_size,
                         enable_k=enable_k, enable_c=enable_c, tm=tm,
                         interpret=(impl == "interpret"))


def squant_flip_batched(w3: jnp.ndarray, scale3: jnp.ndarray, *, bits: int,
                        group_size: Optional[int], enable_k: bool = True,
                        enable_c: bool = True, use_pallas: str = "auto",
                        tm: Optional[int] = None) -> jnp.ndarray:
    """SQuant codes for a (B, M, N) stack of same-shape matrices.

    This is the model-level batched entry point: ``quantize_tree`` stacks all
    same-(shape, dtype) layers of a network into one bucket and issues ONE
    dispatch here instead of one per layer.

    SQuant is row-independent (every stage — E rounding, K group flips, C
    channel flips — operates within a single output channel), so the kernel
    backends flatten the batch into rows and launch the Pallas kernel once
    over ``(B*M, N)``; that is exact, not an approximation. The reference
    backend vmaps the jnp core instead. Which one runs is
    :func:`squant_impl`; the kernel's tile height comes from B*M unless
    ``tm`` overrides it.
    """
    impl = squant_impl(use_pallas, group_size, enable_k, enable_c)
    if impl == "ref":
        return _vmapped_ref(bits, group_size, enable_k, enable_c)(w3, scale3)
    b, m, n = w3.shape
    codes = squant_pallas(w3.reshape(b * m, n), scale3.reshape(b * m, 1),
                          bits=bits, group_size=group_size, enable_k=enable_k,
                          enable_c=enable_c, tm=tm,
                          interpret=(impl == "interpret"))
    return codes.reshape(b, m, n)


@functools.lru_cache(maxsize=None)
def _vmapped_ref(bits: int, group_size: Optional[int], enable_k: bool,
                 enable_c: bool):
    """jit(vmap(squant_ref)) cached per static config — without the outer jit
    the vmap traces through the jnp core op-by-op and per-dispatch overhead
    eats the batching win on small buckets."""
    fn = functools.partial(_ref.squant_ref, bits=bits, group_size=group_size,
                           enable_k=enable_k, enable_c=enable_c)
    return jax.jit(jax.vmap(fn))


def dequant_matmul(x: jnp.ndarray, qt: QuantizedTensor, *,
                   use_pallas: str = "auto", tb: int = 128, tm: int = 128
                   ) -> jnp.ndarray:
    """y = x @ dequant(qt).T for a (out, in)-major QuantizedTensor.

    Per-group scales carry their group size in their shape: ``qt.scale``
    reshaped to (M, NG) covers N in NG equal groups (NG == 1: per channel).
    The kernel path pads the batch up to a whole tile (a multiple of the
    activation dtype's sublane count) instead of shrinking the tile.
    """
    use_pallas = resolve(use_pallas)
    m = qt.shape[0]
    n = math.prod(qt.shape[1:])
    scale = qt.scale.reshape(m, -1)
    if use_pallas == "ref":
        return _ref.dequant_matmul_ref(x, qt.data, scale, bits=qt.bits,
                                       group_size=n // scale.shape[1])
    b = x.shape[0]
    sub = 32 // x.dtype.itemsize                 # 8 rows f32, 16 bf16
    tb_eff = min(tb, -(-b // sub) * sub)
    b_pad = -(-b // tb_eff) * tb_eff
    xp = jnp.pad(x, ((0, b_pad - b), (0, 0))) if b_pad != b else x
    y = dequant_matmul_pallas(xp, qt.data, scale, bits=qt.bits, tb=tb_eff,
                              tm=tm, interpret=(use_pallas == "interpret"))
    return y[:b]
