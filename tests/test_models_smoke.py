"""Per-architecture smoke tests: reduced same-family configs run one forward
/ train step on CPU, asserting output shapes and finiteness; plus
prefill→decode consistency for representative families."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.models.model import build_model

ARCHS = list_archs()


def _batch(cfg, b=2, s=32, key=0):
    k = jax.random.PRNGKey(key)
    tokens = jax.random.randint(k, (b, s), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.is_encdec:
        batch["enc_frames"] = jax.random.normal(
            k, (b, max(1, s // cfg.enc_ratio), cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = get_config(arch, reduced=True)
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": "float32"})
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss, metrics = jax.jit(model.train_loss)(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: loss={loss}"
    assert float(metrics["xent"]) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_grads_finite(arch):
    cfg = get_config(arch, reduced=True)
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": "float32"})
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, b=1, s=16)
    grads = jax.jit(jax.grad(lambda p: model.train_loss(p, batch)[0]))(params)
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in flat), arch
    # at least some gradient is nonzero
    assert any(float(jnp.abs(g).max()) > 0 for g in flat), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_smoke(arch):
    cfg = get_config(arch, reduced=True)
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": "float32"})
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s, extra = 2, 16, 4
    batch = _batch(cfg, b=b, s=s)
    cache = model.init_cache(b, s + extra)
    logits, cache = jax.jit(model.prefill)(params, batch, cache)
    assert logits.shape == (b, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits)))
    step = jax.jit(model.decode_step)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(extra):
        logits, cache = step(params, tok, cache)
        assert logits.shape == (b, cfg.vocab)
        assert np.all(np.isfinite(np.asarray(logits)))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    assert int(cache["pos"]) == s + extra


@pytest.mark.parametrize("arch", ["granite-3-8b", "mixtral-8x7b",
                                  "rwkv6-1.6b", "minicpm3-4b",
                                  "jamba-1.5-large-398b",
                                  "moonshot-v1-16b-a3b"])
def test_decode_matches_teacher_forcing(arch):
    """Prefill+decode logits equal full-sequence forward logits."""
    import dataclasses
    cfg = get_config(arch, reduced=True)
    moe = cfg.moe
    if moe is not None:
        # dropless capacity so teacher forcing and incremental routing agree
        moe = dataclasses.replace(moe, capacity_factor=1e3)
    cfg = dataclasses.replace(cfg, dtype="float32", remat=False, moe=moe)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    b, s = 2, 12
    batch = _batch(cfg, b=b, s=s, key=3)
    # full forward (teacher forcing)
    full_logits, _, _ = jax.jit(
        lambda p, bt: model.forward(p, bt, mode="train"))(params, batch)
    # prefill on the first s-4 tokens, then decode the rest
    cut = s - 4
    pre = {k: (v[:, :cut] if v.ndim >= 2 and v.shape[1] == s else v)
           for k, v in batch.items()}
    cache = model.init_cache(b, s)
    logits, cache = jax.jit(model.prefill)(params, pre, cache)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, cut - 1]),
                               rtol=2e-3, atol=2e-3)
    step = jax.jit(model.decode_step)
    for t in range(cut, s):
        logits, cache = step(params, batch["tokens"][:, t:t + 1], cache)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full_logits[:, t]),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"{arch} step {t}")


def test_swa_ring_buffer_long_prefill():
    """Mixtral-style SWA: prefill longer than the window, then decode."""
    import dataclasses
    cfg = get_config("mixtral-8x7b", reduced=True)
    # serving routes dropless; the training forward it is compared with
    # is made dropless too (capacity above the tokens)
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": "float32",
                           "window": 8, "remat": False,
                           "moe": dataclasses.replace(cfg.moe,
                                                      capacity_factor=1e3)})
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 1, 24          # 3× window
    batch = _batch(cfg, b=b, s=s, key=5)
    full_logits, _, _ = model.forward(params, batch, mode="train")
    cache = model.init_cache(b, s + 8)
    logits, cache = model.prefill(params, batch, cache)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, -1]),
                               rtol=2e-3, atol=2e-3)
    # decode continues coherently (window slides over the ring)
    tok = batch["tokens"][:, -1:]
    logits2, cache = model.decode_step(params, tok, cache)
    assert np.all(np.isfinite(np.asarray(logits2)))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_bf16_dtype_stable(arch):
    """bf16 models must keep scan carries dtype-stable (prefill + decode)."""
    cfg = get_config(arch, reduced=True)   # default dtype bfloat16
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 8
    batch = _batch(cfg, b=b, s=s)
    if cfg.is_encdec:
        batch["enc_frames"] = batch["enc_frames"].astype(jnp.bfloat16)
    cache = model.init_cache(b, s + 2)
    logits, cache = jax.jit(model.prefill)(params, batch, cache)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    step = jax.jit(model.decode_step)
    for _ in range(2):
        logits, cache = step(params, tok, cache)
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))
