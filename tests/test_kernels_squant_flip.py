"""Pallas squant_flip kernel vs pure-jnp oracle: shape/dtype/bits sweeps in
interpret mode (kernel body executes on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core.squant import _ranks_desc
from repro.kernels import ops, ref
from repro.kernels.squant_flip import _select_top_k, tile_rows
from repro.quant.scales import compute_scale


def _case(rng, m, n, dtype=np.float32, scale_mult=1.0):
    w = (rng.normal(size=(m, n)) * scale_mult).astype(dtype)
    return jnp.asarray(w)


@pytest.mark.parametrize("m,n,g", [
    (8, 128, 32),      # exact tiles
    (16, 256, 64),
    (5, 96, 32),       # M padding
    (8, 100, 32),      # N padding
    (3, 50, 16),       # both padded
    (1, 16, 16),       # single row, single group
    (8, 512, 128),     # full-width groups
])
@pytest.mark.parametrize("bits", [4, 8])
def test_pallas_matches_ref_shapes(rng, m, n, g, bits):
    w = _case(rng, m, n)
    scale = compute_scale(w, bits, "max")
    got = ops.squant_flip(w, scale, bits=bits, group_size=g,
                          use_pallas="interpret", tm=4)
    want = ref.squant_ref(w, scale, bits=bits, group_size=g)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_c_stage_tile_not_dividing_m(rng):
    """Many groups: the C stage selects among 300 candidates a row. (Its
    tile once shrank by a VMEM cap to a height that did not divide the
    padded M and left the last rows' gflip unwritten; the cap is gone and
    both passes share the tile height.)"""
    m, g, ng = 8, 4, 300
    w = _case(rng, m, ng * g)
    scale = compute_scale(w, 4, "max")
    got = ops.squant_flip(w, scale, bits=4, group_size=g,
                          use_pallas="interpret", tm=8)
    want = ref.squant_ref(w, scale, bits=4, group_size=g)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_bf16_input_invariants(rng):
    """bf16 inputs produce coarse δ grids with exact .5 ties where summation
    order legitimately differs between implementations — so for bf16 we
    assert the paper's invariants on the kernel output (bit-exactness vs the
    oracle is enforced on the f32 sweeps above)."""
    w = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    scale = compute_scale(w.astype(jnp.float32), 4, "max")
    got = np.asarray(ops.squant_flip(w.astype(jnp.float32), scale, bits=4,
                                     group_size=32, use_pallas="interpret"),
                     np.float64)
    d = got - np.asarray(w, np.float64) / np.asarray(scale)
    assert got.max() <= 7 and got.min() >= -7
    assert np.abs(d).max() < 1.0 + 1e-2
    assert np.abs(d.sum(1)).max() <= 0.5 + 1e-2
    assert np.abs(d.reshape(8, -1, 32).sum(-1)).max() <= 1.0 + 1e-2


@pytest.mark.parametrize("ek,ec", [(False, False), (True, False), (True, True)])
def test_pallas_stage_configs(rng, ek, ec):
    w = _case(rng, 12, 160)
    scale = compute_scale(w, 4, "max")
    got = ops.squant_flip(w, scale, bits=4, group_size=32, enable_k=ek,
                          enable_c=ec, use_pallas="interpret")
    want = ref.squant_ref(w, scale, bits=4, group_size=32, enable_k=ek,
                          enable_c=ec)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_invariants_direct(rng):
    """Invariants hold for the kernel output itself (not just ref-equality)."""
    w = _case(rng, 16, 256)
    scale = compute_scale(w, 4, "max")
    codes = np.asarray(ops.squant_flip(w, scale, bits=4, group_size=64,
                                       use_pallas="interpret"), np.float64)
    d = codes - np.asarray(w) / np.asarray(scale)
    assert np.abs(d.sum(1)).max() <= 0.5 + 1e-4
    assert np.abs(d.reshape(16, -1, 64).sum(-1)).max() <= 1.0 + 1e-4
    assert np.abs(d).max() < 1.0 + 1e-4


def test_pallas_clipping_scale(rng):
    w = _case(rng, 8, 128, scale_mult=4.0)
    scale = jnp.full((8, 1), 0.5, jnp.float32)   # heavy clipping
    got = ops.squant_flip(w, scale, bits=4, group_size=32,
                          use_pallas="interpret")
    want = ref.squant_ref(w, scale, bits=4, group_size=32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got).max() <= 7 and np.asarray(got).min() >= -7


def _select_interpret(score, eligible, k):
    """``_select_top_k`` run as the body of an interpreted Pallas kernel."""
    def kern(s_ref, e_ref, k_ref, o_ref):
        o_ref[...] = _select_top_k(s_ref[...], e_ref[...] > 0,
                                   k_ref[...]).astype(jnp.int32)
    return pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct(score.shape, jnp.int32),
        interpret=True)(jnp.asarray(score), jnp.asarray(eligible, jnp.int32),
                        jnp.asarray(k, jnp.int32))


def _select_oracle(score, eligible, k):
    """The reference's choice: stable double-argsort ranks below k."""
    ranks = np.asarray(_ranks_desc(jnp.where(jnp.asarray(eligible),
                                             jnp.asarray(score), -1.0)))
    return (ranks < k) & eligible


@pytest.mark.parametrize("l", [16, 56, 128])
@pytest.mark.parametrize("case", ["random", "three_values", "k_zero",
                                  "k_all", "none_eligible"])
def test_select_top_k_matches_stable_ranks(rng, l, case):
    """The threshold selection flips exactly the lanes whose stable
    descending rank is below k: ties go to the lower index."""
    r = 16
    if case == "three_values":                  # heavy ties
        score = rng.choice(np.float32([0.125, 0.25, 0.375]), size=(r, l))
    else:                                       # wide exponent range
        score = np.exp(rng.uniform(-30.0, 3.0, size=(r, l)))
    score = score.astype(np.float32)
    eligible = rng.random((r, l)) < 0.6
    if case == "none_eligible":
        eligible[:] = False
    n_elig = eligible.sum(axis=1, keepdims=True)
    if case == "k_zero":
        k = np.zeros((r, 1), np.int64)
    elif case == "k_all":
        k = n_elig
    else:
        k = rng.integers(0, n_elig + 1)
    got = np.asarray(_select_interpret(score, eligible, k)) > 0
    np.testing.assert_array_equal(got, _select_oracle(score, eligible, k))
    np.testing.assert_array_equal(got.sum(axis=1, keepdims=True),
                                  np.minimum(k, n_elig))


@pytest.mark.parametrize("rows,tile", [
    (1, 8), (5, 8), (8, 8), (9, 16), (300, 256), (511, 512), (512, 512),
    (65536, 512), (7168 * 9, 512)])
def test_tile_rows_rule(rows, tile):
    assert tile_rows(rows) == tile


def _tie_heavy(rng, m, n, bits):
    """Weights on a grid of an eighth of the scale (a power of two, so
    ``w / s`` is exact): many ``|δ|`` are equal and nonzero, and the top
    of the grid clips."""
    qmax = 2 ** (bits - 1) - 1
    s = np.float32(2.0 ** -4)
    w = rng.integers(-8 * qmax - 4, 8 * qmax + 5, size=(m, n)) / 8.0 * s
    return jnp.asarray(w.astype(np.float32)), jnp.full((m, 1), s, jnp.float32)


@pytest.mark.parametrize("m,n,g", [
    (300, 256, 128),   # padded M: tile 256, two tiles
    (64, 384, 128),    # exact tile
    (24, 100, 32),     # N padding, tile 16
])
@pytest.mark.parametrize("weights", ["normal", "ties"])
@pytest.mark.parametrize("bits", [4, 8])
def test_pallas_matches_ref_derived_tile(rng, m, n, g, weights, bits):
    """At the tile height derived from M (no override) the codes are the
    reference's, bit for bit, also where many |δ| tie."""
    if weights == "ties":
        w, scale = _tie_heavy(rng, m, n, bits)
    else:
        w = _case(rng, m, n)
        scale = compute_scale(w, bits, "max")
    got = ops.squant_flip(w, scale, bits=bits, group_size=g,
                          use_pallas="interpret")
    want = ref.squant_ref(w, scale, bits=bits, group_size=g)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_batched_derived_tile_matches_ref(rng):
    """The batched entry point flattens B*M = 300 rows and derives its tile
    from them; every matrix equals its own reference."""
    b, m, n, g = 3, 100, 256, 128
    w, scale = _tie_heavy(rng, b * m, n, 4)
    w3, s3 = w.reshape(b, m, n), scale.reshape(b, m, 1)
    got = ops.squant_flip_batched(w3, s3, bits=4, group_size=g,
                                  use_pallas="interpret")
    for i in range(b):
        want = ref.squant_ref(w3[i], s3[i], bits=4, group_size=g)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))
