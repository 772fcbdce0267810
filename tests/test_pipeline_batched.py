"""Batched pipeline (ISSUE 1 tentpole) vs the per-layer reference path.

The contract: bucketing + stacking + one dispatch per bucket + one sync total
must be *bit-exact* against the legacy serial loop for every method, and the
interpret backend (Pallas kernel body on CPU) must match the jnp reference at
the model level.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline
from repro.core.dispatch import BACKENDS, resolve_backend
from repro.core.pipeline import METHODS, quantize_tree
from repro.quant.qtypes import QuantizedTensor


def _tree(rng):
    """2-D dense (two sharing a bucket), 3-D expert, 4-D conv, non-kernels."""
    def w(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32))
    return {
        "blk0": {"attn": {"w": w(24, 32)},
                 "norm": {"gain": jnp.ones((24,), jnp.float32)}},
        "blk1": {"attn": {"w": w(24, 32)}},          # same bucket as blk0
        "head": {"w": w(48, 16)},                    # its own bucket
        "moe": {"w": w(2, 16, 8)},                   # (E, in, out) expert
        "conv": {"w_conv": w(3, 3, 4, 8)},           # (KH, KW, in, out)
        "emb": {"table": w(10, 24)},                 # never quantized
    }


def _qts(tree):
    leaves = jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    return [l for l in leaves if isinstance(l, QuantizedTensor)]


@pytest.mark.parametrize("method", METHODS)
def test_batched_bit_exact_vs_serial(rng, method):
    src = _tree(rng)
    t_b, rep_b = quantize_tree(src, method=method, bits=4, group_size=16,
                               batched=True, backend="ref")
    t_s, rep_s = quantize_tree(src, method=method, bits=4, group_size=16,
                               batched=False)
    qb, qs = _qts(t_b), _qts(t_s)
    assert len(qb) == len(qs) == 5
    for a, b in zip(qb, qs):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a.codes()),
                                      np.asarray(b.codes()))
        np.testing.assert_array_equal(np.asarray(a.scale),
                                      np.asarray(b.scale))
    assert len(rep_b.layers) == len(rep_s.layers) == 5
    # two same-shape dense layers share one bucket
    assert len(rep_b.buckets) == 4
    assert rep_b.total_millis > 0


@pytest.mark.parametrize("method", ("rtn", "squant"))
def test_batched_fake_quant_matches_serial(rng, method):
    src = _tree(rng)
    t_b, _ = quantize_tree(src, method=method, bits=4, group_size=16,
                           dequantize=True, batched=True)
    t_s, _ = quantize_tree(src, method=method, bits=4, group_size=16,
                           dequantize=True, batched=False)
    for a, b in zip(jax.tree_util.tree_leaves(t_b),
                    jax.tree_util.tree_leaves(t_s)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batched_single_sync_serial_per_layer(rng, monkeypatch):
    calls = []
    real = pipeline._sync
    monkeypatch.setattr(pipeline, "_sync",
                        lambda x: (calls.append(1), real(x))[1])
    quantize_tree(_tree(rng), method="squant", bits=4, group_size=16,
                  batched=True)
    assert len(calls) == 1                    # ONE device sync for the tree
    calls.clear()
    quantize_tree(_tree(rng), method="squant", bits=4, group_size=16,
                  batched=False)
    assert len(calls) == 5                    # legacy: one per quantized leaf


def test_interpret_backend_matches_ref(rng):
    """Pallas kernel body (interpret mode) serves the model-level path."""
    def w(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32))
    src = {"a": {"w": w(16, 8)}, "b": {"w": w(16, 8)},
           "conv": {"w_conv": w(2, 2, 4, 8)}}
    for method in ("squant", "squant_ek", "squant_e"):
        t_r, _ = quantize_tree(src, method=method, bits=4, group_size=8,
                               backend="ref")
        t_i, rep_i = quantize_tree(src, method=method, bits=4, group_size=8,
                                   backend="interpret")
        assert rep_i.backend == "interpret"
        for a, b in zip(_qts(t_r), _qts(t_i)):
            np.testing.assert_array_equal(np.asarray(a.codes()),
                                          np.asarray(b.codes()))


def test_backend_resolution():
    assert resolve_backend("ref") == "ref"
    assert resolve_backend("interpret") == "interpret"
    assert resolve_backend("auto") in ("ref", "pallas")
    assert set(BACKENDS) == {"auto", "ref", "pallas", "interpret"}
    with pytest.raises(ValueError):
        quantize_tree({"w": jnp.ones((4, 4))}, backend="cuda")


@pytest.mark.parametrize("entry", ["squant_flip", "quantize_tree",
                                   "dequant_matmul", "paged_attention",
                                   "paged_attention_quant"])
def test_explicit_pallas_never_falls_back(entry):
    """An explicit ``"pallas"`` always compiles the kernel: where it cannot
    lower (here, the CPU backend) the call raises instead of quietly
    running the jnp reference."""
    from repro.kernels import ops
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.paged_attention_quant import paged_attention_quant
    from repro.quant.qtypes import from_codes
    w = jnp.ones((8, 256), jnp.float32)
    s = jnp.ones((8, 1), jnp.float32)
    q = jnp.ones((1, 2, 128), jnp.float32)
    pool = jnp.ones((2, 16, 1, 128), jnp.float32)
    tables, lengths = jnp.ones((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32)
    calls = {
        "squant_flip": lambda: ops.squant_flip(
            w, s, bits=4, group_size=128, use_pallas="pallas"),
        "quantize_tree": lambda: quantize_tree(
            {"w": w.T}, bits=4, group_size=128, backend="pallas"),
        "dequant_matmul": lambda: ops.dequant_matmul(
            w, from_codes(w.astype(jnp.int8), s, 8), use_pallas="pallas"),
        "paged_attention": lambda: paged_attention(
            q, pool, pool, tables, lengths, scale=1.0, use_pallas="pallas"),
        "paged_attention_quant": lambda: paged_attention_quant(
            q, pool.astype(jnp.int8), pool.astype(jnp.int8), pool[..., 0],
            pool[..., 0], tables, lengths, scale=1.0, use_pallas="pallas"),
    }
    assert jax.default_backend() != "tpu"
    with pytest.raises(Exception, match="interpret"):
        jax.block_until_ready(calls[entry]())


def test_bucket_chunking_bit_exact(rng, monkeypatch):
    """A bucket whose stack exceeds the byte cap splits into chunks; results
    stay bit-exact and the tree still syncs once."""
    src = _tree(rng)
    monkeypatch.setattr(pipeline, "_MAX_STACK_BYTES",
                        24 * 32 * 4 + 1)      # one (24,32) f32 layer per chunk
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(pipeline, "_sync",
                        lambda x: (calls.append(1), real(x))[1])
    t_b, rep_b = quantize_tree(src, method="squant", bits=4, group_size=16,
                               batched=True)
    assert len(calls) == 1
    # the (32,24)x2 dense bucket split into two singleton chunks
    assert len(rep_b.buckets) == 5
    t_s, _ = quantize_tree(src, method="squant", bits=4, group_size=16,
                           batched=False)
    for a, b in zip(_qts(t_b), _qts(t_s)):
        np.testing.assert_array_equal(np.asarray(a.codes()),
                                      np.asarray(b.codes()))


def test_report_breakdown(rng):
    _, rep = quantize_tree(_tree(rng), method="squant", bits=4, group_size=16)
    assert rep.total_millis > 0
    assert [b.num_layers for b in rep.buckets] and \
        all(b.num_layers > 0 and b.key and b.impl for b in rep.buckets)
    assert sum(b.num_layers for b in rep.buckets) == len(rep.layers)
    assert "buckets" in rep.summary()
    for lr in rep.layers:
        assert lr.bucket            # every layer names its bucket


@pytest.mark.parametrize("backend,grouped", [
    ("interpret", "interpret"), ("ref", "ref"), ("auto", "ref")])
def test_report_bucket_impl(rng, backend, grouped):
    """Each bucket records what it ran on: grouped SQuant buckets take the
    requested kernel backend; whole-row (``gNone``) buckets, the E&C-only
    ablation and RTN run the jnp reference by design, on every backend
    (on the CPU ``auto`` is the reference)."""
    def w(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32))
    src = {"grouped": {"w": w(32, 8)}, "whole_row": {"w": w(8, 8)}}
    _, rep = quantize_tree(src, method="squant", bits=4, group_size=16,
                           backend=backend)
    impl = {b.key.split(" ")[0]: b.impl for b in rep.buckets}
    assert impl == {"(8,32)x1": grouped, "(8,8)x1": "ref"}
    for method in ("squant_ec", "rtn"):
        _, rep = quantize_tree(src, method=method, bits=4, group_size=16,
                               backend=backend)
        assert {b.impl for b in rep.buckets} == {"ref"}
