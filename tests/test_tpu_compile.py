"""The four Pallas kernels compile for a TPU v5e at published widths.

Nothing runs: each kernel is lowered and compiled against a *described*
``v5e:2x2`` topology, so the TPU compiler's own checks (block tiling,
VMEM, Mosaic lowering) guard every change without a chip. Interpret-mode
tests cannot see these failures. Shapes are granite-3-8b's and
minitron-4b's, plus the two the kernels once got wrong: a prefill batch
that is not a multiple of 8 and SQuant with one group per row.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU compiler library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.paged_attention import paged_decode_pallas
from repro.kernels.squant_flip import squant_pallas
from repro.quant.qtypes import QuantizedTensor


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile cannot be read back from the
    # persistent cache without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compiles_with_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,n,bits,enable_c", [
    (4096, 4096, 4, True),          # granite attention, E&K&C
    (4096, 4096, 8, True),
    (12800, 4096, 4, True),         # granite FFN rows
    (4096, 4096, 4, False),         # E&K only
    (4096, 128, 8, True),           # one group per row
    # rwkv6-1.6b buckets at w4, out-major, stacked: the C stage selects
    # among 56 groups, then 16; the head; a 1 GiB split of the
    # (2048, 2048) bucket. All run 512-row tiles.
    (24 * 2048, 7168, 4, True),
    (24 * 7168, 2048, 4, True),
    (65536, 2048, 4, True),
    (128 * 2048, 2048, 4, True),
])
def test_squant_flip_compiles(spec, m, n, bits, enable_c):
    fn = functools.partial(squant_pallas, bits=bits, group_size=128,
                           enable_c=enable_c)
    _compiles_with_kernel(fn, spec((m, n), jnp.bfloat16),
                          spec((m, 1), jnp.float32))


@pytest.mark.parametrize("bits,groups", [
    (8, 1), (4, 1),                 # per-channel scales (what SQuant makes)
    (8, 32),                        # per-group scales, g128
])
@pytest.mark.parametrize("batch", [8, 148])     # decode; prefill, not 8k
def test_dequant_matmul_compiles(spec, bits, groups, batch):
    m, n = 12800, 4096                          # granite FFN up-projection
    packed = bits <= 4

    def fn(x, codes, scale):
        qt = QuantizedTensor(codes, scale, bits=bits, shape=(m, n))
        return ops.dequant_matmul(x, qt, use_pallas="pallas")

    _compiles_with_kernel(fn, spec((batch, n), jnp.bfloat16),
                          spec((m, n // 2 if packed else n), jnp.int8),
                          spec((m, groups), jnp.float32))


@pytest.mark.parametrize("heads", [24, 32])     # minitron-4b, granite-3-8b
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_attention_compiles(spec, heads, quantized):
    slots, kv, d, bs, context = 8, 8, 128, 16, 2048
    nb = context // bs
    n = slots * nb + 1
    pool = jnp.int8 if quantized else jnp.bfloat16
    args = [spec((slots, heads, d), jnp.bfloat16),
            spec((n, bs, kv, d), pool), spec((n, bs, kv, d), pool),
            spec((slots, nb), jnp.int32), spec((slots,), jnp.int32)]
    if quantized:
        args += [spec((n, bs, kv), jnp.float32)] * 2

    def fn(q, k, v, tables, lengths, *scales):
        return paged_decode_pallas(q, k, v, tables, lengths,
                                   scales if scales else None,
                                   scale=d ** -0.5, interpret=False)

    _compiles_with_kernel(fn, *args)


def test_paged_attention_compiles_at_latent_width(spec):
    """Absorbed MLA decode as the Moonlight cell runs it: 64 slots of 16
    query heads over one latent "KV head" of 576 lanes (kv_lora 512 +
    rope 64), read as keys and values, blocks of 16 to max_len 4096, a
    pool of 16385 blocks."""
    slots, heads, d, bs, context, n = 64, 16, 576, 16, 4096, 16385
    nb = context // bs

    def fn(q, pool, tables, lengths):
        return paged_decode_pallas(q, pool, pool, tables, lengths,
                                   scale=192 ** -0.5, interpret=False)

    _compiles_with_kernel(fn, spec((slots, heads, d), jnp.bfloat16),
                          spec((n, bs, 1, d), jnp.bfloat16),
                          spec((slots, nb), jnp.int32),
                          spec((slots,), jnp.int32))


@pytest.mark.parametrize("s", [1, 4])           # decode; verify of 4 rows
def test_paged_attention_compiles_at_chat_shapes(spec, s):
    """One layer's call in the chat cell: 64 slots of 32 query heads over
    8 KV heads of 128, tables of 192 blocks of 16, a pool of 4097 blocks.
    With ``s`` > 1, the speculative verify's call: row ``b*s+j`` per slot
    and draft position, each slot's table repeated ``s`` times."""
    slots, heads, kv, d, bs, nb, n = 64, 32, 8, 128, 16, 192, 4097

    def fn(q, k, v, tables, lengths):
        return paged_decode_pallas(q, k, v, jnp.repeat(tables, s, axis=0),
                                   lengths, scale=d ** -0.5,
                                   interpret=False)

    _compiles_with_kernel(fn, spec((slots * s, heads, d), jnp.bfloat16),
                          spec((n, bs, kv, d), jnp.bfloat16),
                          spec((n, bs, kv, d), jnp.bfloat16),
                          spec((slots, nb), jnp.int32),
                          spec((slots * s,), jnp.int32))


def test_paged_attention_compiles_at_stored_latent_width(spec):
    """The Moonlight cell's call as the model makes it: latent rows stored
    640 lanes wide (576 rounded up to whole lane tiles, read as keys and
    values), queries padded to match; the kernel copies the pool's pages
    with no copy of the pool before it."""
    slots, heads, d, bs, context, n = 64, 16, 640, 16, 4096, 16385
    nb = context // bs

    def fn(q, pool, tables, lengths):
        return paged_decode_pallas(q, pool, pool, tables, lengths,
                                   scale=192 ** -0.5, interpret=False)

    compiled = jax.jit(fn).lower(
        spec((slots, heads, d), jnp.bfloat16),
        spec((n, bs, 1, d), jnp.bfloat16), spec((slots, nb), jnp.int32),
        spec((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not any(op in line for line in text.splitlines()
                   if f"bf16[{n}," in line for op in (" copy(", " pad("))
