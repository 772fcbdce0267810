"""The latent-attention + experts cell on the CPU: its plain reference
against the program, the configuration check, and a whole tiny run (the
window's counters and metrics, a sound run correct, the control not)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import weights
from conftest import TINY_ARCH
from reference import mla_moe

CELL = "moonlight-16b-a3b-ep8.docqa"
so = harness.load_module("drivers", "serve_mla_moe")
CONF = harness.load_json(harness.HERE / "configs" /
                         "moonlight-16b-a3b-ep8.json")
# limits for these reduced widths, between what sound runs and the control
# read here (the cell's own limit is set from chip readings)
TINY_GAP = 0.6
TINY_CHECK = {"limits": {"logit_gap_max": TINY_GAP}, "sample_requests": 3}


def tiny_arch(**over):
    from repro.models.attention import MLAConfig
    share = so.share_config(CONF)
    return dict(TINY_ARCH, n_layers=3,
                mla=MLAConfig(q_lora=None, kv_lora=16, nope_dim=8,
                              rope_dim=8, v_dim=8, absorb=True),
                moe=dataclasses.replace(share.moe, d_expert=32), **over)


def test_share_config_is_the_published_model_at_its_share():
    cfg = so.share_config(CONF)
    assert (cfg.n_layers, cfg.moe.n_experts, cfg.moe.held,
            cfg.moe.first_held) == (27, 64, 8, 0)
    assert harness.program_config(CONF).moe.held == 64


@pytest.mark.parametrize("key,value", [
    ("kv_lora_rank", 256), ("q_lora_rank", 1536), ("qk_rope_head_dim", 32),
    ("moe_intermediate_size", 1024), ("n_shared_experts", 1),
    ("num_experts_per_tok", 8), ("router_width", 32),
    ("scoring_func", "softmax"), ("routed_scaling_factor", 1.0),
    ("first_k_dense_replace", 3), ("n_group", 8),
    ("intermediate_size", 1408), ("tie_word_embeddings", True),
])
def test_configuration_that_disagrees_with_the_program_is_refused(key,
                                                                   value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        so.share_config(dict(CONF, **{key: value}))


def test_expert_count_of_the_source_must_be_the_router_width():
    with pytest.raises(ValueError, match="router"):
        so.share_config(dict(CONF, reduced={"n_routed_experts": [32, 8]}))


def test_reference_matches_program_forward():
    """The benchmark's reference, fed leaf by leaf, against the program's
    forward (experts dropless: capacity above the tokens) and against the
    program's own plain reference, with a random selection bias."""
    from repro.models import reference
    from repro.models.model import build_model
    cfg = dataclasses.replace(so.share_config(CONF), dtype="float32",
                              scan_layers=False, **tiny_arch())
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=1e3))
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    seed = 2**33 + 5
    params = weights.make_params(shapes, seed, jnp.float32)
    rng = np.random.default_rng(0)
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.asarray(rng.normal(0, 0.3, x.shape), x.dtype)
        if weights.path_names(p)[-1] == "score_bias" else x, params)
    leaves = {weights.path_names(kp): x for kp, x in
              jax.tree_util.tree_flatten_with_path(params)[0]}
    tokens = rng.integers(0, cfg.vocab, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = model.forward(params, {"tokens": jnp.asarray(tokens)})
    plain = reference.forward(params, tokens, cfg)
    layer = so._layer_getter(shapes, lambda nm: leaves[tuple(nm)])
    assert [layer(l)[1] for l in range(3)] == [False, True, True]
    m, e = cfg.mla, cfg.moe
    rcfg = dict(n_heads=cfg.n_heads, nope=m.nope_dim, rope=m.rope_dim,
                v=m.v_dim, kv_lora=m.kv_lora, theta=cfg.rope_theta,
                eps=1e-6, top_k=e.top_k, routed_scale=e.routed_scale,
                first_held=e.first_held)
    x = mla_moe.forward_hidden(tokens, leaves["embedding", "embedding"],
                               layer, cfg.n_layers, rcfg)
    pos = jnp.asarray(np.tile(np.arange(40), (2, 1)))
    got = mla_moe.logits_at(x, pos, leaves["final_norm", "gain"],
                            leaves["lm_head", "w"], eps=1e-6)
    for ref in (want, plain):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=0,
                                   atol=2e-5 * float(np.abs(ref).max()))


def test_latent_attention_cost_by_hand():
    # 1000 latent rows of 576 lanes, 16 heads: scores 2*1000*16*576 and
    # values 2*1000*16*512 operations; each row read once (1000*576*2 B),
    # one query of 16*576 in and an output of 16*512 back, bf16
    f, b = so.latent_attention_cost(1000, 1, 16, 576, 512)
    assert f == 2.0 * 1000 * 16 * (576 + 512)
    assert b == 1000 * 576 * 2 + 16 * (576 + 512) * 2


def test_sound_run_is_correct_and_control_fails(run_tiny):
    rec = run_tiny(CELL, 2**40 + 21, control=True, check=TINY_CHECK,
                   arch=tiny_arch())
    assert rec.correct and rec.attempted > 0
    assert rec.layer["traced_in_window"] == {}
    rows = rec.layer["rows"]
    assert rows and all(len(r) == 12 for r in rows)
    read = {n: harness.load_module("metrics", n).read(rec) for n in (
        "moe_rows_useful_share", "kv_walk_useful_share", "mfu.serve")}
    assert all(v is not None and 0 < v <= 100 for v in read.values()), read
    # about top_k * held / router width of a token's choices are held
    # here, over held rows per token (dropless)
    share = 100.0 * 6 * 8 / 64 / 8
    assert 0.4 * share < read["moe_rows_useful_share"] < 1.2 * share
    gap = rec.checks[0].value
    ctl = rec.extra["control"]["logit_gap_max"]
    assert ctl > TINY_GAP > gap and ctl > 3 * gap


def _stale_decode(eng):
    """A decode step that hands back a copy of the cache it was given
    (the pool it was handed is donated): the new token's latent row is
    never kept."""
    orig = eng._decode

    def step(params, tokens, cache):
        kept = jax.tree_util.tree_map(jnp.copy, cache)
        logits, _ = orig(params, tokens, cache)
        return logits, kept
    eng._decode = step


def test_decode_returning_its_state_unchanged_is_caught(run_tiny):
    rec = run_tiny(CELL, 2**40 + 22, check=TINY_CHECK, arch=tiny_arch(),
                   faults=[_stale_decode])
    assert not rec.correct


def test_selection_bias_is_drawn_from_the_seed():
    from repro.models.model import build_model
    cfg = dataclasses.replace(so.share_config(CONF), scan_layers=False,
                              **tiny_arch())
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    a = so.score_biases(shapes, 2**40 + 3, jnp.bfloat16)
    b = so.score_biases(shapes, 2**40 + 3, jnp.bfloat16)
    c = so.score_biases(shapes, 2**40 + 4, jnp.bfloat16)
    # one bias per MoE layer, over the router's width, differing by expert
    assert len(a) == 2 and all(v.shape == (64,) for v in a.values())
    for nm, v in a.items():
        assert nm[-2:] == ("router", "score_bias")
        assert np.array_equal(v, b[nm]) and not np.array_equal(v, c[nm])
        assert 0.05 < float(jnp.std(v.astype(jnp.float32))) < 0.2
