"""Moonlight-16B-A3B (``moonshot-v1-16b-a3b``: MLA, a leading dense layer,
64 sigmoid-routed experts beside 2 shared ones) against the plain float32
reference (``repro.models.reference``), at a reduced size on seeded
weights, on the CPU.

Tolerances: the served model runs in float32 here, so served and
reference logits differ only by summation order (absorbed vs expanded
attention, the gather kernel's online softmax, dense-over-held experts vs
a per-expert loop): a few float32 ulps of the largest logit, held to
``2e-4 * max|logit|``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_walk import paged_walk_case
from repro.configs import get_config
from repro.kernels.paged_attention import (paged_attention_ref,
                                           paged_decode_pallas)
from repro.models import moe as moe_lib
from repro.models.attention import latent_store_width
from repro.models import reference
from repro.models.model import build_model
from repro.serving.engine import Request, ServeConfig, ServeEngine

ATOL = 2e-4     # times max |logit|: float32 summation order, see above


def _cfg(**over):
    cfg = get_config("moonshot-v1-16b-a3b", reduced=True)
    return dataclasses.replace(cfg, dtype="float32", scan_layers=False,
                               **over)


def _params(cfg, seed=0):
    """Seeded weights with a random selection bias in every router, so
    that the bias really moves which experts are chosen."""
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)

    def bias(path, x):
        if getattr(path[-1], "key", None) == "score_bias":
            return jnp.asarray(rng.normal(0, 0.5, x.shape), x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(bias, params)


def test_published_config():
    cfg = get_config("moonshot-v1-16b-a3b")
    m, e = cfg.mla, cfg.moe
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab) == \
        (27, 2048, 16, 163840)
    assert (m.q_lora, m.kv_lora, m.nope_dim, m.rope_dim, m.v_dim) == \
        (None, 512, 128, 64, 128) and m.absorb
    assert (cfg.first_k_dense, cfg.d_ff, e.d_expert) == (1, 11264, 1408)
    assert (e.n_experts, e.top_k, e.n_shared, e.scoring) == \
        (64, 6, 2, "sigmoid")
    assert e.norm_topk and e.routed_scale == 2.446
    assert not cfg.tie_embeddings and cfg.rope_theta == 50000.0
    # parameters of the published model: 15.96 B; one chip's 8-expert
    # share of every MoE layer: 3.36 B
    shapes = jax.eval_shape(build_model(dataclasses.replace(
        cfg, scan_layers=False)).init, jax.random.PRNGKey(0))
    assert sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(
        shapes)) == pytest.approx(15.96e9, rel=2e-3)
    share = dataclasses.replace(cfg, scan_layers=False,
                                moe=dataclasses.replace(e, n_held=8))
    shapes = jax.eval_shape(build_model(share).init, jax.random.PRNGKey(0))
    assert sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(
        shapes)) == pytest.approx(3.36e9, rel=2e-3)


def test_sigmoid_routing_with_bias_matches_reference():
    """Selection on score plus bias, weights from the scores alone,
    normalised and scaled: the program's router against the reference's,
    with a bias large enough to change the choice."""
    cfg = _cfg()
    rng = np.random.default_rng(1)
    router = {"w": jnp.asarray(rng.normal(0, 0.3, (64, 16)), jnp.float32),
              "score_bias": jnp.asarray(rng.normal(0, 0.5, 16), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    moe = dataclasses.replace(cfg.moe, n_experts=16, top_k=6)
    w, idx, _ = moe_lib.route(router, x, top_k=6, scoring="sigmoid",
                              routed_scale=moe.routed_scale)
    with jax.default_matmul_precision("highest"):
        rw, ridx = reference.route(x @ router["w"], router["score_bias"],
                                   moe)
    assert np.array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), moe.routed_scale,
                               rtol=1e-5)
    # the bias changes the selection (so the test sees it)
    _, idx0, _ = moe_lib.route(dict(router, score_bias=jnp.zeros(16)), x,
                               top_k=6, scoring="sigmoid")
    assert not np.array_equal(np.asarray(idx0), np.asarray(idx))


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight chips hold 2 experts each of 16: the routed parts the eight
    shares compute, plus the shared experts once, add up to the uncut
    reference layer."""
    cfg = _cfg()
    moe = dataclasses.replace(cfg.moe, n_experts=16, top_k=6)
    cfg = dataclasses.replace(cfg, moe=moe)
    d, f = cfg.d_model, moe.d_expert
    p = moe_lib.init_moe(jax.random.PRNGKey(3), d, f, 16, n_routed=16,
                         score_bias=True)
    p["router"]["score_bias"] = jnp.asarray(
        np.random.default_rng(3).normal(0, 0.5, 16), jnp.float32)
    shared = {n: {"w": jax.random.normal(jax.random.PRNGKey(i), s) * 0.1}
              for i, (n, s) in enumerate([("wi", (d, 2 * f)),
                                          ("wg", (d, 2 * f)),
                                          ("wdown", (2 * f, d))])}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, d))
    kw = dict(n_experts=16, top_k=6, dropless=True, scoring="sigmoid",
              routed_scale=moe.routed_scale)
    total, pairs = 0.0, 0
    for c in range(8):
        part = dict(p, **{n: {"w": p[n]["w"][2 * c:2 * c + 2]}
                          for n in ("wi", "wg", "wdown")})
        y, _, n = moe_lib.moe_apply(part, x, first_held=2 * c, n_held=2,
                                    **kw)
        total, pairs = total + y, pairs + n
    from repro.models.ffn import ffn
    total = total + ffn(shared, x, "swiglu")
    with jax.default_matmul_precision("highest"):
        want = reference.moe(x, p, shared, cfg)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=0, atol=1e-5 * float(
                                   np.abs(want).max()))
    # every token's six choices are computed by exactly one share
    assert np.all(np.asarray(pairs) == 6)


def _served_logits(cfg, params, reqs, **serve):
    """Greedy serving; the logits each token was sampled from, by
    ``(request_id, position)``."""
    eng = ServeEngine(build_model(cfg), params, ServeConfig(
        scheduler="continuous", **serve))
    got = {}
    sched = eng.scheduler

    def capture(rid, pos, tok):
        slot = next(i for i, s in enumerate(sched.slots)
                    if s is not None and s.req.request_id == rid)
        got[rid, pos] = np.asarray(sched.kv.logits[slot])
        return None
    sched.token_override = capture
    out = {c.request_id: c.tokens for c in eng.generate(reqs)}
    stats = eng.stats()
    eng.close()
    return out, got, stats


def _requests(lens=(11, 7, 14, 5), new=6):
    rng = np.random.default_rng(5)
    return [Request(prompt=rng.integers(0, 256, n).tolist(),
                    max_new_tokens=new, request_id=i)
            for i, n in enumerate(lens)]


@pytest.mark.parametrize("chunk", [0, 4])
def test_paged_serving_matches_reference_forward(chunk):
    """Prefill (monolithic or in chunks of 4), then absorbed paged decode
    through the latent pool: every served token's logits equal the
    reference's full forward at that position."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _requests()
    toks, got, _ = _served_logits(
        cfg, params, reqs, max_slots=2, max_len=32, kv_backend="paged",
        block_size=4, prefill_chunk=chunk)
    for r in reqs:
        seq = list(r.prompt) + toks[r.request_id]
        ref = np.asarray(reference.forward(params, jnp.asarray([seq]),
                                           cfg))[0]
        for j in range(r.max_new_tokens):
            want = ref[len(r.prompt) - 1 + j]
            np.testing.assert_allclose(
                got[r.request_id, j], want, rtol=0,
                atol=ATOL * float(np.abs(want).max()),
                err_msg=f"request {r.request_id} token {j}")


def test_squant_w8_serving_matches_reference_of_its_codes():
    """Real SQuant w8 codes (``dequantize_for_compute=False``: expert banks
    quantized per expert, ``kv_up`` dequantized for the absorption, the
    other linears through ``dequant_matmul``): served logits equal the
    reference's forward of the same codes, dequantized."""
    from repro.core.pipeline import _restore_dense
    cfg = _cfg()
    params = _params(cfg, seed=2)
    reqs = _requests(lens=(12, 6), new=5)
    eng = ServeEngine(build_model(cfg), params, ServeConfig(
        scheduler="continuous", max_slots=2, max_len=32, kv_backend="paged",
        block_size=4, prefill_chunk=4, quantize_weights="squant",
        weight_bits=8, dequantize_for_compute=False))
    served = eng.params
    eng.close()
    deq = jax.tree_util.tree_map(
        lambda q, fp: _restore_dense(q.dequantize(jnp.float32), fp.shape)
        if hasattr(q, "dequantize") else q, served, params,
        is_leaf=lambda x: hasattr(x, "dequantize"))
    assert any(hasattr(x, "dequantize") for x in jax.tree_util.tree_leaves(
        served["stack"]["list"][0]["b0"]["moe"],
        is_leaf=lambda x: hasattr(x, "dequantize")))
    toks, got, _ = _served_logits(
        cfg, params, reqs, max_slots=2, max_len=32, kv_backend="paged",
        block_size=4, prefill_chunk=4, quantize_weights="squant",
        weight_bits=8, dequantize_for_compute=False)
    for r in reqs:
        seq = list(r.prompt) + toks[r.request_id]
        ref = np.asarray(reference.forward(deq, jnp.asarray([seq]), cfg))[0]
        for j in range(r.max_new_tokens):
            want = ref[len(r.prompt) - 1 + j]
            np.testing.assert_allclose(
                got[r.request_id, j], want, rtol=0,
                atol=ATOL * float(np.abs(want).max()),
                err_msg=f"request {r.request_id} token {j}")


def test_paged_absorbed_decode_equals_contiguous():
    """Equal prompt lengths, admitted in one wave, put every slot at the
    same position in both backends: the paged gather over the latent pool
    and the contiguous absorbed decode give the same logits."""
    cfg = _cfg()
    params = _params(cfg, seed=1)
    reqs = _requests(lens=(9, 9, 9), new=7)
    common = dict(max_slots=3, max_len=32)
    t_c, l_c, _ = _served_logits(cfg, params, reqs, **common)
    t_p, l_p, _ = _served_logits(cfg, params, reqs, kv_backend="paged",
                                 block_size=4, **common)
    assert t_c == t_p
    for k, v in l_c.items():
        np.testing.assert_allclose(l_p[k], v, rtol=0,
                                   atol=1e-6 * float(np.abs(v).max()))


@pytest.mark.parametrize("arch", ["moonshot-v1-16b-a3b", "granite-3-8b"])
def test_donated_pool_serves_the_same_tokens(arch):
    """``donate_kv``: decode, block scatter and copy-on-write update the
    pool in place (the arrays handed in are consumed), and serving gives
    the same tokens and logits as with the pool copied."""
    cfg = dataclasses.replace(get_config(arch, reduced=True),
                              dtype="float32", scan_layers=False)
    params = _params(cfg, seed=3)
    reqs = _requests(lens=(11, 7, 14, 5, 9), new=6)
    common = dict(max_slots=2, max_len=32, kv_backend="paged", block_size=4,
                  prefill_chunk=4)
    t_k, l_k, _ = _served_logits(cfg, params, reqs, **common)
    t_d, l_d, _ = _served_logits(cfg, params, reqs, donate_kv=True,
                                 **common)
    assert t_d == t_k
    for k, v in l_k.items():
        np.testing.assert_array_equal(l_d[k], v)
    eng = ServeEngine(build_model(cfg), params, ServeConfig(
        scheduler="continuous", donate_kv=True, **common))
    eng.generate(reqs[:1])
    kv = eng.scheduler.kv
    before = jax.tree_util.tree_leaves(kv._cache["list"])
    eng.generate(reqs[1:2])
    assert all(x.is_deleted() for x in before)
    eng.close()


def test_latent_rows_page_and_stay_bounded():
    """The paged pool holds one latent row per position: width kv_lora +
    rope rounded up to whole 128-lane tiles, with no head axis (the pool
    is ``model.init_cache(blocks, block_size)``)."""
    cfg = _cfg()
    eng = ServeEngine(build_model(cfg), _params(cfg), ServeConfig(
        scheduler="continuous", max_slots=2, max_len=32,
        kv_backend="paged", block_size=4))
    eng.generate(_requests(lens=(6, 5), new=3))
    pool = eng.scheduler.kv._cache
    m = cfg.mla
    assert pool["lead"][0]["latent"].shape == \
        (2 * 8 + 1, 4, latent_store_width(m))
    assert latent_store_width(m) == -(-(m.kv_lora + m.rope_dim) // 128) * 128
    assert eng.scheduler.kv.bytes_per_position() == \
        cfg.n_layers * latent_store_width(m) * 4
    eng.close()


@pytest.mark.parametrize("over,match", [
    (dict(speculative=True, kv_backend="paged"), "speculative x MLA"),
    (dict(quantize_kv=True), "quantize_kv x MLA"),
])
def test_mla_gates(over, match):
    cfg = _cfg()
    with pytest.raises(NotImplementedError, match=match):
        ServeEngine(build_model(cfg), _params(cfg), ServeConfig(
            scheduler="continuous", max_slots=2, max_len=32, **over))


@pytest.mark.parametrize("heads,context", [
    (16, 64), (4, 48),
    # lengths 0, P*bs - 1, P*bs and nb*bs - 1, nb not a multiple of P
    pytest.param(16, "edges", id="16-edges"),
    # the same, with every position no slot attends (the trash block, the
    # last page past each length) holding NaN and 1e30 lanes
    pytest.param(16, "poison", id="16-poison"),
])
def test_paged_kernel_at_latent_width(heads, context):
    """The gather kernel (interpret mode) over a latent pool of width 576,
    read as keys and as values, against the jnp gather."""
    rng = np.random.default_rng(heads)
    slots, bs, d = 3, 16, 576
    if isinstance(context, int):
        nb = context // bs
        n = slots * nb + 1
        tables = jnp.asarray(rng.permutation(np.arange(1, n))
                             .reshape(slots, nb), jnp.int32)
        lengths = jnp.asarray([context - 1, 5, 17], jnp.int32)
    else:
        tables, lengths, n, dead, _ = paged_walk_case(1, d, bs, 4)
        slots = len(lengths)
        tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    pool = jnp.asarray(rng.normal(size=(n, bs, 1, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(slots, heads, d)), jnp.float32)
    scale = 192 ** -0.5
    got = paged_decode_pallas(q, pool, pool, tables, lengths, scale=scale,
                              interpret=True)
    want = paged_attention_ref(q, pool, pool, tables, lengths, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    if context == "poison":
        lanes = np.where(np.arange(d) % 2, np.nan, 1e30)
        bad = jnp.asarray(np.where(dead[:, :, None, None], lanes,
                                   np.asarray(pool)), jnp.float32)
        out = paged_decode_pallas(q, bad, bad, tables, lengths, scale=scale,
                                  interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(got))
