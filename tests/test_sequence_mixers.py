"""Equivalence tests for the sequence mixers: chunked vs step-scan RWKV,
associative-scan vs step-decode Mamba, and MoE routing properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.mamba import init_mamba, init_mamba_state, mamba
from repro.models.moe import init_moe, moe_apply
from repro.models.rwkv import wkv_chunked, wkv_scan


@pytest.mark.parametrize("chunk", [16, 32])
def test_wkv_chunked_equals_scan(rng, chunk):
    b, h, s, d = 2, 3, 64, 16
    r, k, v = (jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
               * 0.5 for _ in range(3))
    logw = jnp.asarray(
        -np.exp(rng.normal(size=(b, h, s, d)).astype(np.float32) * 0.3 - 1.0)
    ).clip(-2.0, -1e-4)
    u = jnp.asarray(rng.normal(size=(h, d)).astype(np.float32) * 0.1)
    s0 = jnp.asarray(rng.normal(size=(b, h, d, d)).astype(np.float32) * 0.1)
    o1, sf1 = wkv_scan(r, k, v, logw, u, s0)
    o2, sf2 = wkv_chunked(r, k, v, logw, u, s0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(sf1), np.asarray(sf2), rtol=1e-4,
                               atol=1e-4)


def test_wkv_state_carries_across_calls(rng):
    """Processing [a; b] equals processing a then b with the carried state."""
    b, h, s, d = 1, 2, 32, 8
    def mk():
        return jnp.asarray(
            rng.normal(size=(b, h, s, d)).astype(np.float32)) * 0.5
    r, k, v = mk(), mk(), mk()
    logw = jnp.clip(mk() - 1.0, -2.0, -1e-4)
    u = jnp.asarray(rng.normal(size=(h, d)).astype(np.float32) * 0.1)
    s0 = jnp.zeros((b, h, d, d), jnp.float32)
    o_full, sf_full = wkv_scan(r, k, v, logw, u, s0)
    half = s // 2
    o1, s1 = wkv_scan(r[:, :, :half], k[:, :, :half], v[:, :, :half],
                      logw[:, :, :half], u, s0)
    o2, s2 = wkv_scan(r[:, :, half:], k[:, :, half:], v[:, :, half:],
                      logw[:, :, half:], u, s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 2)),
                               np.asarray(o_full), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(sf_full),
                               rtol=1e-5, atol=1e-5)


def test_mamba_decode_matches_prefill(rng):
    d, n = 32, 8
    p = init_mamba(jax.random.PRNGKey(0), d, d_state=n)
    x = jnp.asarray(rng.normal(size=(2, 16, d)).astype(np.float32))
    y, _ = mamba(p, x, d_state=n, mode="prefill")
    st = init_mamba_state(2, d, d_state=n)
    outs = []
    for t in range(16):
        yt, st = mamba(p, x[:, t:t + 1], d_state=n, state=st, mode="decode")
        outs.append(yt)
    yd = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yd), rtol=1e-3,
                               atol=1e-3)


def test_mamba_prefill_state_continues(rng):
    d, n = 16, 4
    p = init_mamba(jax.random.PRNGKey(1), d, d_state=n)
    x = jnp.asarray(rng.normal(size=(1, 24, d)).astype(np.float32))
    y_full, _ = mamba(p, x, d_state=n, mode="prefill")
    _, st = mamba(p, x[:, :16], d_state=n, mode="prefill")
    y2, _ = mamba(p, x[:, 16:17], d_state=n, state=st, mode="decode")
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, 16:17]),
                               rtol=1e-3, atol=1e-3)


def test_moe_output_is_gated_expert_mix(rng):
    """With top_k == n_experts and dropless capacity, MoE equals the
    softmax-weighted sum of all expert FFNs."""
    d, ff, e = 16, 32, 4
    p = init_moe(jax.random.PRNGKey(0), d, ff, e, kind="relu")
    x = jnp.asarray(rng.normal(size=(2, 8, d)).astype(np.float32))
    y, _, _ = moe_apply(p, x, n_experts=e, top_k=e, kind="relu",
                        dropless=True)
    xt = x.reshape(-1, d)
    probs = jax.nn.softmax(xt @ p["router"]["w"], axis=-1)
    ref = jnp.zeros_like(xt)
    for ei in range(e):
        h = jax.nn.relu(xt @ p["wi"]["w"][ei])
        ref += probs[:, ei:ei + 1] * (h @ p["wdown"]["w"][ei])
    np.testing.assert_allclose(np.asarray(y.reshape(-1, d)), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_moe_capacity_drops_tokens(rng):
    """Tiny capacity → some tokens bypass experts (output 0 for them)."""
    d, ff, e = 8, 16, 4
    p = init_moe(jax.random.PRNGKey(0), d, ff, e, kind="relu")
    x = jnp.asarray(rng.normal(size=(1, 64, d)).astype(np.float32))
    y_full, _, _ = moe_apply(p, x, n_experts=e, top_k=2, kind="relu",
                             dropless=True)
    y_tight, _, _ = moe_apply(p, x, n_experts=e, top_k=2, kind="relu",
                              capacity_factor=0.25)
    # tight capacity must zero some token outputs that dropless serves
    changed = np.abs(np.asarray(y_full - y_tight)).max(-1) > 1e-6
    assert changed.any()
    aux = moe_apply(p, x, n_experts=e, top_k=2, kind="relu")[1]
    assert float(aux) > 0
