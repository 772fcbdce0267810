"""Paged KV cache × chunked admission (PR 7).

The lifted gate: under ``kv_backend="paged"`` a chunked admission has no
shared clock to catch up to — each pending entry's completion target is
its OWN prompt length, chunks run on a 1-row side cache at monolithic-
admission shapes (batch 1, unpadded), and completion scatters into the
slot's reserved blocks. Tokens are therefore position-deterministic:
bit-identical to monolithic paged AND the solo contiguous oracle for
EVERY chunk split, regardless of admission timing.

The bug-shaped seams this file pins down:

* force-swap abandon must release reserved blocks and unpin shared-prefix
  blocks (the contiguous abandon just drops the side cache — under paged
  that leaks until pool exhaustion);
* shared-prefix blocks must be pinned BEFORE the first chunk step, so
  FIFO eviction under pool pressure between chunk steps can never recycle
  a block the pending gathered from.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build_model
from repro.serving.engine import Request, ServeConfig, ServeEngine


def _tiny(seed=0, vocab=256, **over):
    cfg = get_config("granite-3-8b", reduced=True)
    cfg = dataclasses.replace(cfg, dtype="float32", n_layers=2, d_model=32,
                              n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
                              vocab=vocab, **over)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def _paged(model, params, **over):
    base = dict(max_len=32, scheduler="continuous", max_slots=2,
                kv_backend="paged", block_size=4)
    base.update(over)
    return ServeEngine(model, params, ServeConfig(**base))


def _solo_oracle(model, params, reqs, max_len=32):
    out = {}
    for r in reqs:
        eng = ServeEngine(model, params,
                          ServeConfig(max_batch=1, max_len=max_len))
        out[r.request_id] = eng.generate([r])[0].tokens
    return out


def _kv_stats(eng):
    return eng.scheduler.stats()["kv"]


def _assert_block_invariant(eng):
    """The stats()-level block invariant (free + cached + active + trash
    == num_blocks) plus the full internal consistency check."""
    kv = _kv_stats(eng)
    assert (kv["blocks_free"] + kv["blocks_cached"] + kv["blocks_active"]
            + kv["blocks_trash"]) == kv["blocks_total"]
    eng.scheduler.kv.check_invariants()


def _assert_no_leaks(eng):
    kv = _kv_stats(eng)
    assert kv["blocks_active"] == 0
    assert kv["blocks_reserved"] == 0
    _assert_block_invariant(eng)


def _stage_at_step(eng, step, params2):
    def hook(info):
        if info["step"] == step and not eng.store.staged_pending:
            eng.store.stage(fp_params=params2, source="midrun", block=True)
    eng.on_step = hook


# ---------------------------------------------------------------------------
# property-style chunk-split sweep: bit-identity for every split
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sweep_setup():
    model, params = _tiny()
    # mixed lengths + staggered budgets over 2 slots: retirements
    # interleave, so later admissions happen mid-flight while a resident
    # decodes (the case the contiguous backend cannot chunk at chunk=1)
    reqs = [Request(prompt=[1, 2, 3, 4, 5, 6], max_new_tokens=8,
                    request_id=0),
            Request(prompt=[7, 8], max_new_tokens=3, request_id=1),
            Request(prompt=[9, 10, 11, 12, 13, 14, 15, 16, 17, 18, 19],
                    max_new_tokens=5, request_id=2),
            Request(prompt=[4, 3, 2], max_new_tokens=6, request_id=3)]
    oracle = _solo_oracle(model, params, reqs)
    mono = {c.request_id: c.tokens
            for c in _paged(model, params).generate(reqs)}
    return model, params, reqs, oracle, mono


@pytest.mark.parametrize("chunk", [1, 3, 5, 64])
def test_chunk_split_sweep_bit_identical(sweep_setup, chunk):
    """chunk=1 (every chunk a padded singleton), chunk=3/5 (non-dividing),
    chunk=64 (>= every prompt: one-chunk pendings) — all bit-identical to
    monolithic paged and the solo contiguous oracle."""
    model, params, reqs, oracle, mono = sweep_setup
    eng = _paged(model, params, prefill_chunk=chunk)
    outs = eng.generate(reqs)
    for c in outs:
        assert c.tokens == oracle[c.request_id], f"chunk={chunk} vs oracle"
        assert c.tokens == mono[c.request_id], f"chunk={chunk} vs monolithic"
    sch = eng.scheduler.stats()
    assert sch["admitted"] == 4 and sch["retired"] == 4
    assert sch["pendings_started"] >= 2       # fresh wave + mid-flight
    assert sch["pendings_abandoned"] == 0
    expected_chunks = sum(-(-len(r.prompt) // chunk) for r in reqs)
    # prefix reuse can only shrink suffixes, never add chunk steps
    assert 0 < sch["chunk_steps"] <= expected_chunks
    _assert_no_leaks(eng)


def test_midflight_admission_with_residents_chunk1():
    """The headline case the contiguous backend cannot serve: a long
    prompt admitted at chunk=1 while a resident decodes. No catch-up
    recurrence — the pending completes at its own prompt length after
    exactly ceil(L/1) chunk steps."""
    model, params = _tiny()
    resident = Request(prompt=[1, 2], max_new_tokens=14, request_id=0)
    long_req = Request(prompt=list(range(2, 15)), max_new_tokens=4,
                       request_id=1)
    oracle = _solo_oracle(model, params, [resident, long_req])
    eng = _paged(model, params, max_slots=1, prefill_chunk=1)
    outs = eng.generate([resident, long_req])
    for c in outs:
        assert c.tokens == oracle[c.request_id]
    adm = {e["request_id"]: e for e in eng.scheduler.admission_log}
    assert adm[1]["chunks"] == len(long_req.prompt)
    assert adm[1]["clock"] == len(long_req.prompt)   # per-slot position
    _assert_no_leaks(eng)


def test_trace_counts_one_trace_per_chunk_length():
    """One ``prefill_chunk`` trace per distinct chunk width (jit keys on
    the input shape, so a singleton chunk is its own specialization even
    though it pads to two rows inside the trace), one decode trace, zero
    monolithic prefills — and a repeated same-shape run adds no traces."""
    model, params = _tiny()
    reqs = [Request(prompt=[11, 12, 13, 14, 15, 16, 17], max_new_tokens=3,
                    request_id=0),
            Request(prompt=[21, 22, 23, 24, 25], max_new_tokens=3,
                    request_id=1)]
    eng = _paged(model, params, prefill_chunk=3)
    eng.generate(reqs)
    tc = eng.trace_counts
    # widths: 7 -> 3,3,1; 5 -> 3,2  => {3, 2, 1}
    assert tc["prefill"] == 0
    assert tc["prefill_chunk"] == 3
    assert tc["decode"] == 1
    assert eng.scheduler.stats()["chunk_steps"] == 3 + 2
    # second run re-chunks the unshared suffixes through the registry;
    # the third repeats the second's shapes exactly: zero new traces
    eng.generate(reqs)
    snap = dict(eng.trace_counts)
    eng.generate(reqs)
    assert eng.trace_counts == snap
    _assert_no_leaks(eng)


def test_shared_prefix_chat_turn_chunks_suffix_only():
    """A second turn sharing the first turn's prompt gathers the pinned
    full prefix blocks (8 of 10 tokens — the partial tail block is freed
    with its owning slot) and chunk-prefills only the remaining 5-token
    suffix: ceil(5/2) = 3 chunks instead of ceil(13/2) = 7."""
    model, params = _tiny()
    turn1 = Request(prompt=list(range(1, 11)), max_new_tokens=4,
                    request_id=0)
    turn2 = Request(prompt=list(range(1, 11)) + [51, 52, 53],
                    max_new_tokens=4, request_id=1)
    oracle = _solo_oracle(model, params, [turn1, turn2])
    eng = _paged(model, params, prefill_chunk=2)
    assert eng.generate([turn1])[0].tokens == oracle[0]
    outs = eng.generate([turn2])
    assert outs[0].tokens == oracle[1]
    kv = _kv_stats(eng)
    assert kv["prefix_hits"] >= 1
    assert kv["prefix_tokens_reused"] >= 8
    adm = [e for e in eng.scheduler.admission_log if e["request_id"] == 1]
    assert adm[-1]["chunks"] == 3
    _assert_no_leaks(eng)


# ---------------------------------------------------------------------------
# force-swap abandon: reserved blocks released, prefix pins dropped
# ---------------------------------------------------------------------------

def test_repeated_force_swap_abandons_release_blocks_and_pins():
    """A deadline force-swap abandons the in-flight pending entry while it
    holds shared-prefix pins and a full block reservation. Repeatedly: the
    block invariant must hold after every abandon (the leak this PR fixes
    — reserved blocks and pin refcounts used to survive the abandon), and
    the re-admitted request's tokens must match the solo oracle on the new
    weights."""
    model, params = _tiny(0)
    staged_params = [_tiny(s)[1] for s in (1, 2, 3)]
    resident = Request(prompt=list(range(1, 9)), max_new_tokens=12,
                       request_id=0)
    eng = _paged(model, params, prefill_chunk=1, swap_deadline_ms=0.0)
    for it, p2 in enumerate(staged_params):
        # per-iteration suffix: a repeated tail would be fully registered
        # by the previous iteration, shrinking the pending below the
        # staging step
        tail = [61 + 10 * it + j for j in range(6)]
        follower = Request(prompt=list(range(1, 9)) + tail,
                           max_new_tokens=4, request_id=1)
        # fresh wave: entry 0 (resident) completes and decodes while entry
        # 1 (follower, 8-token shared prefix -> 2 pinned blocks, 6-token
        # suffix at chunk=1) is mid-pending when the stage lands at step 2
        _stage_at_step(eng, eng.scheduler.steps_total + 2, p2)
        outs = eng.generate([resident, follower])
        sch = eng.scheduler.stats()
        assert sch["pendings_abandoned"] == it + 1
        assert sch["forced_swaps"] == it + 1
        assert outs[0].forced_swaps == 1
        # re-admitted post-swap on the new version, chunked from scratch
        # (the registry flushed with the swap), still oracle-identical
        assert outs[1].weights_version == outs[0].weights_version + 1
        oracle = _solo_oracle(model, p2, [follower])
        assert outs[1].tokens == oracle[1]
        assert len(outs[0].tokens) == resident.max_new_tokens
        _assert_no_leaks(eng)


def test_drain_waits_on_paged_pending_no_abandon():
    """With no deadline, a staged version drains the pending like any
    in-flight work: every entry completes on the old version, nothing is
    abandoned, and the block accounting stays clean."""
    model, params = _tiny(0)
    _, params2 = _tiny(1)
    reqs = [Request(prompt=[1, 2, 3], max_new_tokens=3, request_id=0),
            Request(prompt=list(range(5, 17)), max_new_tokens=8,
                    request_id=1),
            Request(prompt=[21, 22], max_new_tokens=4, request_id=2)]
    eng = _paged(model, params, prefill_chunk=2, swap_deadline_ms=None)
    _stage_at_step(eng, 2, params2)
    outs = eng.generate(reqs)
    sch = eng.scheduler.stats()
    assert sch["pendings_abandoned"] == 0
    assert sch["forced_swaps"] == 0
    assert all(len(o.tokens) == r.max_new_tokens
               for o, r in zip(outs, reqs))
    _assert_no_leaks(eng)


# ---------------------------------------------------------------------------
# pin-before-first-chunk vs FIFO eviction under pool pressure
# ---------------------------------------------------------------------------

def test_pins_survive_eviction_between_chunk_steps():
    """White-box: begin a chunked admission over a registered prefix, then
    exhaust the pool between its chunk steps. Eviction may only take the
    UNPINNED cached block; with nothing evictable left, allocation must
    fail loudly rather than recycle a pinned block — and the pending still
    completes with the oracle's greedy continuation."""
    model, params = _tiny()
    eng = _paged(model, params, max_slots=2, prefill_chunk=1)
    seed_req = Request(prompt=list(range(1, 13)), max_new_tokens=4,
                       request_id=0)
    eng.generate([seed_req])                  # registers 3 full blocks
    kv = eng.scheduler.kv
    assert _kv_stats(eng)["blocks_cached"] == 3

    follow = Request(prompt=list(range(1, 9)) + [41, 42, 43],
                     max_new_tokens=4, request_id=1)
    params_tree = eng.store.acquire()[0].params
    kv.reserve_pending(0, follow)
    lp, side = kv.begin_chunked_admit(0, follow)
    assert lp == 8                            # 2 of the 3 blocks pinned
    assert _kv_stats(eng)["blocks_cached"] == 1

    # pool pressure between chunk steps: drain the free list, then force
    # one eviction — it must take the unpinned cached block, after which
    # the pool is exhausted (pinned blocks are NOT evictable)
    taken = [kv._alloc() for _ in range(len(kv._free))]
    evicted = kv._alloc()
    taken.append(evicted)
    assert kv.evictions == 1
    assert _kv_stats(eng)["blocks_cached"] == 0
    with pytest.raises(RuntimeError, match="exhausted"):
        kv._alloc()
    for ph in taken:                          # release the pressure
        kv._unref(ph)

    logits = None
    for t in follow.prompt[lp:]:
        toks = jnp.asarray(np.asarray([[t]], np.int32))
        out, side = eng._prefill_chunk(params_tree, {"tokens": toks},
                                       side)
        logits = out.logits
    kv.complete_chunked_admit(0, follow, lp, side, logits)
    kv.check_invariants()
    # the pinned prefix survived the eviction: the slot's first greedy
    # token matches the solo oracle's
    oracle = _solo_oracle(model, params, [follow])
    assert int(np.argmax(np.asarray(kv.logits[0]))) == oracle[1][0]
    kv.retire(0)
    _assert_no_leaks(eng)


def test_eviction_pressure_end_to_end_tokens_still_identical():
    """End-to-end: a pool sized so resident decode allocations must evict
    the one unpinned cached block while a shared-prefix chunked admission
    is in flight. Eviction happens (the pool is exactly one block short),
    tokens stay oracle-identical, and the accounting balances."""
    model, params = _tiny()
    eng = _paged(model, params, max_slots=2, kv_blocks=9, prefill_chunk=1)
    seed_req = Request(prompt=list(range(1, 13)), max_new_tokens=4,
                       request_id=0)
    oracle0 = _solo_oracle(model, params, [seed_req])
    assert eng.generate([seed_req])[0].tokens == oracle0[0]
    assert _kv_stats(eng)["blocks_cached"] == 3

    resident = Request(prompt=list(range(21, 27)), max_new_tokens=10,
                       request_id=1)
    follow = Request(prompt=list(range(1, 9)) + [41, 42, 43, 44, 45, 46],
                     max_new_tokens=2, request_id=2)
    oracle = _solo_oracle(model, params, [resident, follow])
    outs = eng.generate([resident, follow])
    for c in outs:
        assert c.tokens == oracle[c.request_id]
    kv = _kv_stats(eng)
    assert kv["evictions"] >= 1
    assert kv["prefix_hits"] >= 1
    _assert_no_leaks(eng)
