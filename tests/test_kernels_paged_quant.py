"""Fused int8 dequant paged-attention kernel tests: interpret-mode parity
vs the jnp reference on mixed lengths and GQA head ratios, trash-block /
masked-column exactness with poisoned codes AND scales, closeness to the
fp paged oracle when the pools come from ``_quant_tok``, and the
quantizer's own hardening properties (all-zero rows, extreme magnitudes,
round-trip bound, vmap/jit friendliness, no int8 wrap)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_walk import paged_walk_case
from repro.kernels.paged_attention import paged_attention_ref
from repro.kernels.paged_attention_quant import (paged_attention_quant,
                                                 paged_attention_quant_ref)
from repro.models.attention import _quant_tok


def _rand_quant_pools(key, nblocks, bs, kv, d):
    """fp pools quantized per-(position, head) with the serving quantizer
    (the exact write path both backends use)."""
    k1, k2 = jax.random.split(key)
    k_fp = jax.random.normal(k1, (nblocks, bs, kv, d), jnp.float32)
    v_fp = jax.random.normal(k2, (nblocks, bs, kv, d), jnp.float32)
    kq, ks = _quant_tok(k_fp)
    vq, vs = _quant_tok(v_fp)
    return k_fp, v_fp, kq, ks, vq, vs


# ---------------------------------------------------------------------------
# interpret-mode parity vs the jnp reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rep,case", [             # MQA → GQA head ratios
    pytest.param(1, "mixed", id="1"),
    pytest.param(2, "mixed", id="2"),
    pytest.param(4, "mixed", id="4"),
    # lengths 0, P*bs - 1, P*bs and nb*bs - 1, nb not a multiple of P
    pytest.param(2, "edges", id="edges"),
    # the same, with every position no slot attends (the trash block, the
    # last page past each length) holding extreme codes and NaN and 1e30
    # scales
    pytest.param(2, "poison", id="poison"),
])
def test_quant_kernel_matches_ref(rep, case):
    key = jax.random.PRNGKey(0)
    b, kv, d, bs, nb_slot, nblocks = 3, 2, 16, 4, 6, 20
    h = kv * rep
    ks_ = jax.random.split(key, 3)
    if case == "mixed":
        bt = jax.random.randint(ks_[2], (b, nb_slot), 1, nblocks) \
            .astype(jnp.int32)
        lengths = jnp.asarray([0, 7, 21], jnp.int32)   # mixed fills
    else:
        bs = 8
        bt, lengths, nblocks, dead, _ = paged_walk_case(kv, d, bs, 1)
        b = len(lengths)
        bt, lengths = jnp.asarray(bt), jnp.asarray(lengths)
    q = jax.random.normal(ks_[0], (b, h, d), jnp.float32)
    _, _, kq, ksc, vq, vsc = _rand_quant_pools(ks_[1], nblocks, bs, kv, d)
    scale = 1.0 / np.sqrt(d)
    ref = paged_attention_quant_ref(q, kq, vq, ksc, vsc, bt, lengths,
                                    scale=scale)
    ker = paged_attention_quant(q, kq, vq, ksc, vsc, bt, lengths,
                                scale=scale, use_pallas="interpret")
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    if case == "poison":
        bad = jnp.asarray(dead)[:, :, None]
        out = paged_attention_quant(
            q, jnp.where(bad[..., None], jnp.int8(127), kq),
            jnp.where(bad[..., None], jnp.int8(-127), vq),
            jnp.where(bad, jnp.nan, ksc), jnp.where(bad, 1e30, vsc), bt,
            lengths, scale=scale, use_pallas="interpret")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ker))


def test_quant_ref_masks_trash_columns_and_scales():
    """Columns past a row's length must contribute exactly zero even when
    their codes AND scales are poisoned — the mask, not zero-initialized
    scales, is what protects never-written pool positions."""
    key = jax.random.PRNGKey(1)
    b, kv, d, bs, nb_slot, nblocks = 2, 1, 8, 4, 3, 8
    ks_ = jax.random.split(key, 3)
    q = jax.random.normal(ks_[0], (b, kv, d), jnp.float32)
    _, _, kq, ksc, vq, vsc = _rand_quant_pools(ks_[1], nblocks, bs, kv, d)
    bt = jax.random.randint(ks_[2], (b, nb_slot), 1, nblocks) \
        .astype(jnp.int32)
    lengths = jnp.asarray([2, 9], jnp.int32)
    # poison every pool position past each row's length
    dead = np.ones((nblocks, bs), bool)
    bt_np, ln_np = np.asarray(bt), np.asarray(lengths)
    for r in range(b):
        for j in range(nb_slot):
            for o in range(bs):
                if j * bs + o <= ln_np[r]:
                    dead[bt_np[r, j], o] = False
    assert dead.any()
    poison_c = jnp.where(jnp.asarray(dead)[:, :, None, None],
                         jnp.full_like(kq, 127), kq)
    poison_v = jnp.where(jnp.asarray(dead)[:, :, None, None],
                         jnp.full_like(vq, -127), vq)
    poison_ks = jnp.where(jnp.asarray(dead)[:, :, None],
                          jnp.full_like(ksc, 1e6), ksc)
    poison_vs = jnp.where(jnp.asarray(dead)[:, :, None],
                          jnp.full_like(vsc, 1e6), vsc)
    # each implementation is compared against ITS OWN unpoisoned output
    # (ref vs interpret only agree to float tolerance, masking is exact)
    for fn, kwargs in ((paged_attention_quant_ref, {}),
                       (paged_attention_quant,
                        {"use_pallas": "interpret"})):
        base = fn(q, kq, vq, ksc, vsc, bt, lengths, scale=0.35, **kwargs)
        out = fn(q, poison_c, poison_v, poison_ks, poison_vs, bt, lengths,
                 scale=0.35, **kwargs)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


def test_quant_ref_close_to_fp_oracle():
    """Quantized pools built by ``_quant_tok`` must reproduce the fp paged
    oracle within int8 round-trip noise — the closeness the serving-level
    0.98 greedy-agreement budget rides on."""
    key = jax.random.PRNGKey(2)
    b, kv, rep, d, bs, nb_slot, nblocks = 2, 2, 2, 16, 4, 4, 12
    h = kv * rep
    ks_ = jax.random.split(key, 3)
    q = jax.random.normal(ks_[0], (b, h, d), jnp.float32)
    k_fp, v_fp, kq, ksc, vq, vsc = _rand_quant_pools(ks_[1], nblocks, bs,
                                                     kv, d)
    bt = jax.random.randint(ks_[2], (b, nb_slot), 1, nblocks) \
        .astype(jnp.int32)
    lengths = jnp.asarray([5, 15], jnp.int32)
    scale = 1.0 / np.sqrt(d)
    fp = paged_attention_ref(q, k_fp, v_fp, bt, lengths, scale=scale)
    qn = paged_attention_quant_ref(q, kq, vq, ksc, vsc, bt, lengths,
                                   scale=scale)
    err = np.abs(np.asarray(fp) - np.asarray(qn)).max()
    ref_mag = np.abs(np.asarray(fp)).max()
    assert err <= 0.05 * ref_mag, (err, ref_mag)


# ---------------------------------------------------------------------------
# _quant_tok hardening (satellite: all-zero rows, extremes, vmap/jit)
# ---------------------------------------------------------------------------

def test_quant_tok_round_trip_extreme_magnitudes():
    """Property over extreme rows: round-trip error <= 0.5 * scale per
    element, no NaN/Inf, and codes never wrap past +/-127."""
    rows = np.stack([
        np.zeros(8, np.float32),                     # all-zero row
        np.full(8, 1e-30, np.float32),               # below the scale floor
        np.full(8, -1e-30, np.float32),
        np.linspace(-1e30, 1e30, 8).astype(np.float32),
        np.asarray([1e30] + [0.0] * 7, np.float32),  # one huge outlier
        np.asarray([-1e-6, 1e-6] * 4, np.float32),   # at the floor
        np.linspace(-3.0, 3.0, 8).astype(np.float32),
    ])
    x = jnp.asarray(rows)[None, :, None, :]          # (1, S, KV=1, D)
    codes, scale = _quant_tok(x)
    codes_np, scale_np = np.asarray(codes, np.int32), np.asarray(scale)
    assert np.isfinite(scale_np).all()
    assert codes_np.min() >= -127 and codes_np.max() <= 127
    deq = codes_np.astype(np.float64) * scale_np[..., None]
    assert np.isfinite(deq).all()
    err = np.abs(deq - np.asarray(x, np.float64))
    assert (err <= 0.5 * scale_np[..., None] + 1e-38).all(), err.max()


def test_quant_tok_zero_rows_exact():
    codes, scale = _quant_tok(jnp.zeros((2, 3, 2, 4)))
    assert np.all(np.asarray(codes) == 0)
    assert np.isfinite(np.asarray(scale)).all()
    deq = np.asarray(codes, np.float32) * np.asarray(scale)[..., None]
    np.testing.assert_array_equal(deq, np.zeros((2, 3, 2, 4), np.float32))


def test_quant_tok_vmap_jit_any_leading_shape():
    """One quantizer for both backends: contiguous writes (B, S, KV, D)
    rows, the paged decode path quantizes (B, 1, KV, D) — and it must
    compose with vmap/jit without shape-specific branches."""
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 5, 2, 8), jnp.float32)
    c_direct, s_direct = _quant_tok(x)
    c_vmap, s_vmap = jax.jit(jax.vmap(_quant_tok))(x)
    # jit fusion may reorder the abs-max reduction by ~1 ulp, so compare
    # the dequantized values (codes can flip only at exact .5 boundaries)
    assert c_vmap.dtype == jnp.int8 and s_vmap.shape == s_direct.shape
    np.testing.assert_allclose(
        np.asarray(c_vmap, np.float32) * np.asarray(s_vmap)[..., None],
        np.asarray(c_direct, np.float32) * np.asarray(s_direct)[..., None],
        rtol=1e-5, atol=1e-6)
    # 3D leading shape (pool-shaped input) works too
    c_pool, s_pool = jax.jit(_quant_tok)(x.reshape(15, 2, 8))
    assert c_pool.dtype == jnp.int8 and s_pool.shape == (15, 2)
    np.testing.assert_allclose(
        np.asarray(c_pool, np.float32) * np.asarray(s_pool)[..., None],
        (np.asarray(c_direct, np.float32)
         * np.asarray(s_direct)[..., None]).reshape(15, 2, 8),
        rtol=1e-5, atol=1e-6)
