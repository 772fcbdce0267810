"""Quantized tensor container + int4 packing + quantization-run reports.

A ``QuantizedTensor`` is a pytree holding integer codes plus dequantization
scales. It is the on-disk / in-memory serving format produced by every
quantizer in this framework (SQuant and the baselines alike). The report
dataclasses at the bottom (``QuantReport`` and friends) are the wall-time /
dispatch / shard accounting emitted by ``core.pipeline.quantize_tree`` and
consumed by the launch CLIs and benchmarks.

Conventions
-----------
* Codes are symmetric signed integers in ``[-qmax, qmax]`` with
  ``qmax = 2**(bits-1) - 1`` (paper's uniform symmetric grid).
* ``scale`` broadcasts against the *output-channel* (row) dimension:
  per-channel scale has shape ``(M, 1)``; per-group ``(M, G_count)`` where the
  code tensor is logically ``(M, G_count, group_size)``.
* 4-bit codes are stored packed two-per-byte in an int8 carrier
  (little-nibble-first) to honour the real memory footprint.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def qmax_for_bits(bits: int) -> int:
    if not 2 <= bits <= 8:
        raise ValueError(f"bits must be in [2, 8], got {bits}")
    return 2 ** (bits - 1) - 1


@jax.jit
def _pack_int4_jit(codes: jax.Array) -> jax.Array:
    lo = codes[..., 0::2].astype(jnp.int8)
    hi = codes[..., 1::2].astype(jnp.int8)
    return ((hi << 4) | (lo & 0x0F)).astype(jnp.int8)


def pack_int4(codes: jax.Array) -> jax.Array:
    """Pack int8 codes in [-8, 7] into int8 bytes, two nibbles per byte.

    Last dim must be even. Little-nibble-first: out[..., i] holds codes
    (2i) in bits 0-3 and (2i+1) in bits 4-7. Jitted: the strided slices are
    gather ops that dominate quantization wall time when run eagerly.
    """
    if codes.shape[-1] % 2 != 0:
        raise ValueError(f"last dim must be even, got {codes.shape}")
    return _pack_int4_jit(codes)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`; returns sign-extended int8 codes."""
    lo = (packed << 4).astype(jnp.int8) >> 4  # arithmetic shift sign-extends
    hi = packed >> 4
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*packed.shape[:-1], packed.shape[-1] * 2)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """Integer codes + scales. ``data`` is int8 (packed when bits==4)."""

    data: jax.Array           # int8; (M, N) or (M, N//2) when packed
    scale: jax.Array          # f32; broadcastable to (M, groups)
    bits: int = 8
    group_size: Optional[int] = None   # None → per-channel scale
    shape: tuple = ()                  # logical (unpacked) shape

    def tree_flatten(self):
        return (self.data, self.scale), (self.bits, self.group_size, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scale = children
        bits, group_size, shape = aux
        return cls(data=data, scale=scale, bits=bits, group_size=group_size,
                   shape=shape)

    @property
    def packed(self) -> bool:
        return self.bits <= 4

    def codes(self) -> jax.Array:
        """Unpacked int8 codes with logical shape."""
        n = int(np.prod(self.shape[1:]))
        if self.packed:
            flat = unpack_int4(self.data).reshape(self.shape[0], -1)
            return flat[:, :n].reshape(self.shape)
        return self.data.reshape(self.shape)

    def dequantize(self, dtype=jnp.float32) -> jax.Array:
        c = self.codes().astype(jnp.float32)
        m = self.shape[0]
        rest = int(np.prod(self.shape[1:]))
        if self.group_size is None:
            w = c.reshape(m, rest) * self.scale.reshape(m, 1)
        else:
            g = self.group_size
            ngroups = rest // g
            w = (c.reshape(m, ngroups, g)
                 * self.scale.reshape(m, ngroups, 1)).reshape(m, rest)
        return w.reshape(self.shape).astype(dtype)

    def nbytes(self) -> int:
        """True serving footprint in bytes (codes + scales)."""
        return int(np.prod(self.data.shape)) + 4 * int(np.prod(self.scale.shape))

    def with_placement(self, data_sharding, scale_sharding
                       ) -> "QuantizedTensor":
        """The same tensor with codes/scales placed on the given shardings
        (asynchronous ``device_put`` — no host sync)."""
        return QuantizedTensor(
            data=jax.device_put(self.data, data_sharding),
            scale=jax.device_put(self.scale, scale_sharding),
            bits=self.bits, group_size=self.group_size, shape=self.shape)


def is_quantized_tree(tree) -> bool:
    """True if any leaf of ``tree`` is a :class:`QuantizedTensor`."""
    leaves = jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    return any(isinstance(x, QuantizedTensor) for x in leaves)


# ---------------------------------------------------------------------------
# Quantization-run reports (filled by core.pipeline.quantize_tree)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LayerReport:
    path: str
    shape: Tuple[int, ...]
    millis: float              # batched mode: amortized bucket dispatch time
    method: str
    bits: int
    bucket: str = ""           # bucket key this layer was quantized in


@dataclasses.dataclass
class BucketReport:
    key: str                   # "(M, N)xB dtype gG"
    num_layers: int
    impl: str = ""             # what ran: "pallas" | "interpret" | "ref"


@dataclasses.dataclass
class ShardReport:
    """Per-device row accounting for the sharded (``mesh=``) pipeline."""
    device: int                # position along the sharded mesh axis
    rows: int                  # real weight rows quantized on this device
    pad_rows: int              # padding rows added so the axis divides


@dataclasses.dataclass
class QuantReport:
    layers: List[LayerReport]
    total_millis: float
    method: str
    bits: int
    backend: str = "ref"
    buckets: List[BucketReport] = dataclasses.field(default_factory=list)
    mesh_axis: str = ""        # sharded runs: name of the partitioned axis
    mesh_size: int = 1         # devices along that axis (1 → unsharded)
    shards: List[ShardReport] = dataclasses.field(default_factory=list)

    def summary(self) -> str:
        s = (f"{self.method} w{self.bits}: {len(self.layers)} layers in "
             f"{self.total_millis:.1f} ms "
             f"({self.total_millis / max(len(self.layers), 1):.2f} ms/layer)")
        if self.buckets:
            s += f" [{len(self.buckets)} buckets, backend={self.backend}]"
        if self.mesh_size > 1:
            rows = sum(sh.rows for sh in self.shards)
            s += (f" [sharded {self.mesh_axis}={self.mesh_size}, "
                  f"{rows} rows]")
        return s


def from_codes(codes: jax.Array, scale: jax.Array, bits: int,
               group_size: Optional[int] = None) -> QuantizedTensor:
    """Build a QuantizedTensor from unpacked integer codes."""
    shape = tuple(codes.shape)
    m = shape[0]
    flat = codes.reshape(m, -1).astype(jnp.int8)
    if bits <= 4:
        if flat.shape[-1] % 2:
            flat = jnp.pad(flat, ((0, 0), (0, 1)))
        data = pack_int4(flat)
    else:
        data = flat
    return QuantizedTensor(data=data, scale=scale.astype(jnp.float32),
                           bits=bits, group_size=group_size, shape=shape)
