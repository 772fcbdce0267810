"""Per-architecture chunked-prefill agreement: the PR-10 gate lift.

Every decoder-only architecture in the registry now runs ``prefill_chunk
> 0`` on the continuous scheduler. Plain-attention dense stacks stay
bit-identical (covered by the existing scheduler tests); the stacks swept
here — sliding-window rings, MLA latent caches, MoE capacity routing,
mamba/rwkv recurrent state — are tolerance-equivalent instead, each held
to its measured ``AGREEMENT_BUDGETS`` floor via teacher-forced greedy
agreement against a monolithic-prefill oracle (methodology in
``docs/equivalence.md``). The sweep covers chunk widths 1 (slowest
catch-up), a non-dividing width, a width at least the prompt length
(single-chunk admission), and a mid-flight admission into a freed slot.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import ArchConfig, MoEConfig, get_config
from repro.models.model import build_model
from repro.serving.engine import Request, ServeConfig, ServeEngine
from repro.serving.equivalence import (AGREEMENT_BUDGETS, active_budget_keys,
                                       agreement_budget,
                                       greedy_token_agreement, oracle_tokens)

# The plain-attention MoE stack: 4 experts, top-2 of the softmax, every
# layer (what the reduced moonshot-v1-16b-a3b was before it became the
# published MLA + shared-expert model).
PLAIN_MOE = ArchConfig(
    name="plain-moe", family="moe", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=4, head_dim=16, d_ff=128, vocab=256,
    moe=MoEConfig(n_experts=4, top_k=2))

# label -> (registry arch or explicit config, shrink overrides). Mirrors
# the ``CHUNKED_ARCH_ROWS`` ladder in benchmarks/bench_serving.py: the
# jamba row isolates the mamba mixer; the mixtral row is the composed
# sliding_window x moe stack; the moonshot row composes MLA, a leading
# dense layer, and sigmoid-routed experts beside shared ones.
ARCHS = {
    "sliding_window": ("granite-3-8b", dict(n_layers=2, window=8)),
    "mla": ("minicpm3-4b", dict(n_layers=2)),
    "moe": (PLAIN_MOE, {}),
    "mamba": ("jamba-1.5-large-398b",
              dict(n_layers=2, block_pattern=("m", "a"), moe=None)),
    "rwkv": ("rwkv6-1.6b", dict(n_layers=2)),
    "sliding_window+moe": ("mixtral-8x7b", dict(n_layers=2, window=8)),
    "mla+moe": ("moonshot-v1-16b-a3b", dict(n_layers=3)),
}

_MODELS = {}


def _model(label):
    if label not in _MODELS:
        name, over = ARCHS[label]
        base = name if isinstance(name, ArchConfig) \
            else get_config(name, reduced=True)
        cfg = dataclasses.replace(base, dtype="float32", **over)
        m = build_model(cfg)
        _MODELS[label] = (m, m.init(jax.random.PRNGKey(0)))
    return _MODELS[label]


def _wave():
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(4):
        plen = int(rng.integers(5, 10))
        prompt = [int(t) for t in rng.integers(1, 200, size=plen)]
        reqs.append(Request(prompt=prompt, max_new_tokens=8, request_id=i))
    return reqs


@pytest.mark.parametrize("label", sorted(ARCHS))
def test_chunk_split_sweep_within_budget(label):
    """chunk in {1, non-dividing, >= prompt}: a fresh admission wave's
    teacher-forced agreement vs the monolithic oracle stays at or above
    the architecture's composed budget; budget 1.0 means every compared
    token matched (exact identity)."""
    model, params = _model(label)
    reqs = _wave()
    base = ServeConfig(max_batch=4, max_len=48, scheduler="continuous")
    oracle_eng = ServeEngine(model, params, base)
    oracle = oracle_tokens(oracle_eng.generate(reqs))
    oracle_eng.close()
    for chunk in (1, 3, 16):
        cfg = dataclasses.replace(base, prefill_chunk=chunk)
        budget = agreement_budget(cfg, model.cfg)
        eng = ServeEngine(model, params, cfg)
        rep = greedy_token_agreement(eng, reqs, oracle)
        eng.close()
        assert rep.compared == sum(r.max_new_tokens for r in reqs)
        rep.assert_budget(budget, f"{label} chunk={chunk}")


@pytest.mark.parametrize("label", sorted(ARCHS))
def test_midflight_chunked_admission_within_budget(label):
    """A chunked admission into a freed slot commits to clock P and
    left-pads to P; its tokens agree with the round engine run at the
    same padding (filler-pinned) within the architecture's budget —
    the mid-flight leg of the sweep."""
    model, params = _model(label)
    reqs = [Request(prompt=[1, 2, 3], max_new_tokens=2, request_id=0),
            Request(prompt=[5, 6, 7, 8, 9], max_new_tokens=12,
                    request_id=1),
            Request(prompt=[11, 12, 13], max_new_tokens=8, request_id=2)]
    ccfg = ServeConfig(max_batch=2, max_len=64, scheduler="continuous",
                       prefill_chunk=2)
    cont = ServeEngine(model, params, ccfg)
    cont.generate(reqs)     # discover request 2's admission clock
    adm = {e["request_id"]: e for e in cont.scheduler.admission_log}
    clock = adm[2]["clock"]
    assert adm[2]["chunks"] > 1          # genuinely multi-chunk admission
    rnd = ServeEngine(model, params, ServeConfig(max_batch=2, max_len=64))
    ctrl = rnd.generate(
        [Request(prompt=reqs[2].prompt, max_new_tokens=8, request_id=2),
         Request(prompt=[3] * clock, max_new_tokens=1, request_id=99)])
    rnd.close()
    # teacher-force only the late request against its equal-padding oracle
    rep = greedy_token_agreement(cont, reqs, {2: list(ctrl[0].tokens)})
    cont.close()
    assert rep.compared == 8
    rep.assert_budget(agreement_budget(ccfg, model.cfg),
                      f"{label} mid-flight")


def test_mla_chunked_identity():
    """MLA's budget is 1.0 (whole-cache latent re-expansion reproduced
    the monolithic expansion exactly at serving widths) — so its chunked
    tokens owe full identity, not just a rate."""
    model, params = _model("mla")
    reqs = _wave()
    base = ServeConfig(max_batch=4, max_len=48, scheduler="continuous")
    assert agreement_budget(
        dataclasses.replace(base, prefill_chunk=3), model.cfg) == 1.0
    oracle_eng = ServeEngine(model, params, base)
    mono = {c.request_id: c.tokens for c in oracle_eng.generate(reqs)}
    oracle_eng.close()
    eng = ServeEngine(model, params,
                      dataclasses.replace(base, prefill_chunk=3))
    chunked = {c.request_id: c.tokens for c in eng.generate(reqs)}
    eng.close()
    assert chunked == mono


@pytest.mark.parametrize("label", ["sliding_window", "mamba", "rwkv"])
def test_paged_backend_still_gated_for_non_positional_caches(label):
    """The paged backend requires per-position cache rows; rings and
    recurrent state stay gated (engine.ARCH_GATES) with a pointer to the
    contiguous backend. MLA latent rows page (tests/test_moonlight.py)."""
    model, params = _model(label)
    with pytest.raises(NotImplementedError, match="paged KV cache"):
        ServeEngine(model, params,
                    ServeConfig(max_batch=2, max_len=32,
                                scheduler="continuous",
                                kv_backend="paged", block_size=8))


def test_agreement_budget_composes_multiplicatively():
    """The regression the satellite pins: ``agreement_budget`` used to be
    a binary int8_kv-or-exact lookup, silently handing stacked features
    the wrong floor. It now multiplies every active key's floor."""
    mixtral = dataclasses.replace(get_config("mixtral-8x7b", reduced=True),
                                  dtype="float32", n_layers=2, window=8)
    chunked_quant = ServeConfig(max_batch=2, max_len=32,
                                scheduler="continuous", prefill_chunk=4,
                                quantize_kv=True)
    assert active_budget_keys(chunked_quant, mixtral) == \
        ["int8_kv", "sliding_window", "moe"]
    expect = (AGREEMENT_BUDGETS["int8_kv"]
              * AGREEMENT_BUDGETS["sliding_window"]
              * AGREEMENT_BUDGETS["moe"])
    assert agreement_budget(chunked_quant, mixtral) \
        == pytest.approx(expect)
    assert agreement_budget(chunked_quant, mixtral) \
        == pytest.approx(0.79135)     # pinned: 0.98 * 0.95 * 0.85
    # arch keys only activate under chunk-continuation prefill
    mono = dataclasses.replace(chunked_quant, prefill_chunk=0)
    assert agreement_budget(mono, mixtral) == AGREEMENT_BUDGETS["int8_kv"]
    # ... which includes the paged backend's suffix continuations
    dense = dataclasses.replace(get_config("granite-3-8b", reduced=True),
                                dtype="float32", n_layers=2)
    moe = dataclasses.replace(PLAIN_MOE, dtype="float32")
    paged = ServeConfig(max_batch=2, max_len=32, scheduler="continuous",
                        kv_backend="paged", block_size=8)
    assert agreement_budget(paged, moe) == AGREEMENT_BUDGETS["moe"]
    moonshot = dataclasses.replace(
        get_config("moonshot-v1-16b-a3b", reduced=True), dtype="float32")
    assert agreement_budget(paged, moonshot) == pytest.approx(
        AGREEMENT_BUDGETS["mla"] * AGREEMENT_BUDGETS["moe"])
    assert agreement_budget(paged, dense) == 1.0
    # legacy single-argument form (serve-config keys only) still works
    assert agreement_budget(chunked_quant) == AGREEMENT_BUDGETS["int8_kv"]
    assert agreement_budget(mono) == AGREEMENT_BUDGETS["int8_kv"]
    assert agreement_budget(ServeConfig(max_batch=2, max_len=32)) == 1.0
