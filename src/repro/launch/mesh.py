"""Production mesh factories.

Functions, not module-level constants — importing this module never touches
jax device state (smoke tests must keep seeing one device).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.distributed import compat


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh: 16×16 per pod, 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False):
    """Scaled-down mesh (8 devices) with the production topology, for CI
    subprocess tests."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_quantize_mesh(n_devices: Optional[int] = None):
    """1-axis 'data' mesh for sharded quantization (``quantize_tree(mesh=)``).

    SQuant's flip objective is row-independent, so quantization parallelism
    is pure row DP: one flat 'data' axis over however many devices the host
    sees (or the first ``n_devices`` of them).
    """
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if not 1 <= n <= len(devices):
        raise ValueError(f"requested {n} devices, host has {len(devices)}")
    return compat.make_mesh((n,), ("data",), devices=devices[:n])
