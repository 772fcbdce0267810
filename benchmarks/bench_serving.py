"""Serving-scheduler benchmark: round vs continuous batching.

Two experiments on a mixed-length workload (short requests interleaved
with a few long ones — the shape that static rounds serve worst, because
every request in a round waits for the round's longest):

* **throughput** — end-to-end useful tokens/s for the same workload under
  ``scheduler="round"`` vs ``scheduler="continuous"`` (acceptance:
  continuous ≥ 1.2x);
* **reload dip** — a weight version is staged mid-run (a *native* serving
  tree, so staging itself is ~free and the measurement isolates the
  *scheduling* cost of landing a reload, complementing
  ``bench_reload.py``'s staging-contention dip). Per-step useful-token
  rates around the stage→swap window give each engine's decode dip and
  swap lag: the round engine can only swap after its longest in-flight
  request finishes, the continuous engine drains admission and force-swaps
  after ``swap_deadline_ms``;
* **prefill tail** — resident slots decode long budgets while long-prompt
  requests are admitted mid-flight. Monolithic admission stalls every
  resident for the full prefill (the p99 decode step-time spike);
  ``prefill_chunk`` consumes the same prompt a bounded chunk per step.
  Both paths pad the long prompts to the same clock, so their greedy
  tokens must be bit-identical (verified) — the chunked path buys its
  p50/p95/p99 step-time profile for free.
* **shared prefix** — a chat-shaped serial-turn workload: every turn
  carries the same long system prompt plus a short distinct user suffix.
  The contiguous backend re-prefills the full prompt every turn; the
  paged backend (``kv_backend="paged"``) finds the system prompt in its
  block registry and prefills only the suffix. Greedy tokens must be
  bit-identical between the backends (verified); reported are the
  throughput ratio (acceptance: paged ≥ 1.3x), the prefix hit rate, and
  resident KV bytes per context token.
* **paged chunked admission** — the shared-prefix shape under mid-flight
  admission: residents decode while turns carrying a long registered
  prefix plus a long unshared suffix admit into the cycle slot. On the
  paged backend every pending's target is its own prompt length, so
  chunked admission works at any chunk size mid-flight and its greedy
  tokens must be bit-identical to monolithic paged admission (verified);
  the chunked/monolithic p99 decode step-time ratio is held to the same
  bar as the contiguous chunked-prefill experiment.
* **kv bytes** — 32-slot paged decode with an fp32 KV pool vs an int8
  one (``quantize_kv=True``, the fused dequant-attention kernel path).
  Decode at production slot counts is roofline-bound on KV-cache HBM
  bytes per token; the int8 pool moves ``2*D + 8`` bytes per (position,
  kv-head, layer) instead of ``2*D*itemsize`` (acceptance: ≤ 0.6x fp).
  Quantized-KV tokens are NOT bit-identical to fp — the tolerance-
  equivalence harness measures teacher-forced greedy-token agreement vs
  the fp paged oracle instead (hard floor: ≥ 0.98).

* **speculative** — self-speculative decoding on the paged backend: a
  lower-bit squant quantization of the checkpoint drafts ``draft_k``-token
  runs per slot, the squant-w8 serving tree verifies all positions in one
  batched forward, the longest matching prefix is accepted. Greedy
  acceptance makes the output tokens bit-identical to w8-only decode
  (hard-asserted for every draft bit-width measured); reported are the
  w4..w7 acceptance-rate ladder, the p50/p95 accepted run length, and
  the headline throughput/steps ratio vs w8-only (acceptance: throughput
  ≥ 1.0x — every accepted draft saves a full scheduler step's dispatch +
  host logits sync).

Writes ``BENCH_serving.json`` (or ``--smoke`` scale for the CI bench
gate, compared against the committed baseline by
``scripts/check_bench.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from typing import Dict, List

import jax
import numpy as np

from repro.configs import get_config
from repro.models.model import build_model
from repro.serving.engine import Request, ServeConfig, ServeEngine

DIP_WINDOW = 6          # steps per useful-rate window


def _swap_deadline_ms(smoke: bool) -> float:
    """Continuous force-swap deadline for the reload bench: a handful of
    decode steps at each scale (tiny-model steps are ~4x cheaper)."""
    return 1.5 if smoke else 8.0


def _model(smoke: bool):
    cfg = get_config("granite-3-8b", reduced=True)
    over = dict(dtype="float32")
    if smoke:
        over.update(n_layers=2, d_model=32, n_heads=2, n_kv_heads=1,
                    head_dim=16, d_ff=64, vocab=256)
    cfg = dataclasses.replace(cfg, **over)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def mixed_workload(smoke: bool) -> List[Request]:
    """Mostly-short requests with one long request per round-sized chunk,
    so every static round is dominated by its longest member."""
    n, slots = (10, 4) if smoke else (24, 8)
    long_budget, short_budgets = (24, (3, 4, 6)) if smoke \
        else (64, (6, 8, 10, 12))
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(n):
        budget = long_budget if i % slots == 0 \
            else short_budgets[i % len(short_budgets)]
        plen = int(rng.integers(3, 11))
        prompt = [int(t) for t in rng.integers(1, 60, size=plen)]
        reqs.append(Request(prompt=prompt, max_new_tokens=budget,
                            request_id=i))
    return reqs


def _serve_cfg(scheduler: str, smoke: bool, **over) -> ServeConfig:
    slots = 4 if smoke else 8
    return ServeConfig(max_batch=slots, max_len=96 if smoke else 192,
                       scheduler=scheduler, **over)


def bench_throughput(smoke: bool = False, repeats: int = 3,
                     report=print) -> Dict:
    model, params = _model(smoke)
    reqs = mixed_workload(smoke)
    total_tokens = sum(r.max_new_tokens for r in reqs)
    out: Dict = {"requests": len(reqs), "useful_tokens": total_tokens}
    for scheduler in ("round", "continuous"):
        eng = ServeEngine(model, params, _serve_cfg(scheduler, smoke))
        eng.generate(reqs)                       # warm every jit shape
        steps0 = eng.stats()["scheduler"]["steps"]
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            outs = eng.generate(reqs)
            best = min(best, time.perf_counter() - t0)
        assert sum(len(o.tokens) for o in outs) == total_tokens
        steps = eng.stats()["scheduler"]["steps"] - steps0
        eng.close()
        out[scheduler] = {"tok_s": total_tokens / best,
                          "wall_ms": best * 1e3,
                          "steps_per_run": steps // repeats}
        report(f"[serving] {scheduler:10s}: {out[scheduler]['tok_s']:7.0f} "
               f"tok/s ({out[scheduler]['wall_ms']:.0f} ms, "
               f"{out[scheduler]['steps_per_run']} steps)")
    out["ratio"] = out["continuous"]["tok_s"] / out["round"]["tok_s"]
    report(f"[serving] continuous/round throughput ratio: "
           f"{out['ratio']:.2f}x")
    return out


def _dip_metrics(steps: List[dict], stage_idx: int,
                 w: int = DIP_WINDOW) -> Dict:
    """Windowed useful-token rates around the stage→swap interval."""
    rec = [e["recorded"] for e in steps]
    v0 = steps[0]["version"]
    swap_idx = next((i for i, e in enumerate(steps) if e["version"] > v0),
                    None)
    if swap_idx is None:
        raise RuntimeError(
            f"swap never observed in the {len(steps)}-step log (staged at "
            f"step {stage_idx}) — stage earlier or grow the workload")
    steady = sum(rec[max(0, stage_idx - w):stage_idx]) \
        / min(w, max(1, stage_idx))
    hi = min(len(rec) - w, swap_idx + w)
    rates = [sum(rec[i:i + w]) / w
             for i in range(stage_idx, max(stage_idx + 1, hi))]
    min_rate = min(rates)
    return {"steady_rate": steady, "min_rate": min_rate,
            "dip_pct": 100.0 * (1.0 - min_rate / steady),
            "swap_lag_steps": swap_idx - stage_idx}


def bench_reload_dip(smoke: bool = False, report=print) -> Dict:
    model, params = _model(smoke)
    params2 = model.init(jax.random.PRNGKey(1))
    reqs = mixed_workload(smoke)
    stage_step = 5 if smoke else 12
    deadline = _swap_deadline_ms(smoke)
    out: Dict = {"stage_step": stage_step, "swap_deadline_ms": deadline}
    for scheduler in ("round", "continuous"):
        eng = ServeEngine(model, params,
                          _serve_cfg(scheduler, smoke,
                                     swap_deadline_ms=deadline))
        eng.generate(reqs)                       # warm every jit shape
        marks: Dict = {}
        orig_acquire = eng.store.acquire

        def acquire(orig=orig_acquire, marks=marks):
            ver, sms = orig()
            if ver.version >= 2 and "t_swap" not in marks:
                marks["t_swap"] = time.perf_counter()
            return ver, sms

        eng.store.acquire = acquire

        def hook(info, eng=eng, marks=marks):
            if info["step"] == marks["stage_at"] \
                    and "t_stage" not in marks:
                # native serving tree: staging is ~free, isolating the
                # *scheduling* dip from bench_reload's contention dip
                eng.store.stage(serving_params=params2, source="bench",
                                block=True)
                marks["t_stage"] = time.perf_counter()

        eng.on_step = hook
        marks["stage_at"] = eng.scheduler.steps_total + stage_step
        eng.scheduler.step_log = steps = []
        outs = eng.generate(reqs)
        assert sum(len(o.tokens) for o in outs) \
            == sum(r.max_new_tokens for r in reqs)
        m = _dip_metrics(steps, stage_step)
        m["swap_lag_ms"] = (marks["t_swap"] - marks["t_stage"]) * 1e3
        if scheduler == "continuous":
            m["forced_swaps"] = eng.stats()["scheduler"]["forced_swaps"]
        eng.close()
        out[scheduler] = m
        report(f"[serving] reload {scheduler:10s}: steady "
               f"{m['steady_rate']:.1f} tok/step → min {m['min_rate']:.1f} "
               f"(dip {m['dip_pct']:.0f}%), swap lag "
               f"{m['swap_lag_steps']} steps / {m['swap_lag_ms']:.1f} ms")
    out["dip_advantage_pct"] = \
        out["round"]["dip_pct"] - out["continuous"]["dip_pct"]
    report(f"[serving] continuous reload dip is "
           f"{out['dip_advantage_pct']:.0f} pts smaller than round")
    return out


def _tail_model():
    """A wider LM for the prefill-tail experiment: at toy widths both
    prefill and decode are pure dispatch overhead, so the admission spike
    chunking bounds would be invisible. This width makes a long-prompt
    prefill FLOPs-bound (~2-4x a decode step) while a single chunk stays
    well under one."""
    cfg = get_config("granite-3-8b", reduced=True)
    cfg = dataclasses.replace(cfg, dtype="float32", d_model=384, d_ff=1024,
                              n_heads=4, n_kv_heads=2, head_dim=64,
                              vocab=512)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def long_prompt_workload(smoke: bool):
    """Three background residents with 240-token prompts (the pool clock
    starts deep, so every later admission pays a long prefill) decode while
    a cycle slot serves one short request and then a sequence of ever-longer
    long-prompt requests admitted mid-flight. Each long prompt length is
    derived from the scheduler's own catch-up recurrence, so the chunked
    path's committed completion clock lands exactly on the prompt length —
    which is also the first clock the monolithic path can admit it at:
    identical padding in both paths, hence bit-identical greedy tokens.
    Admission spikes are ~4% of steps, putting p99 squarely on them.
    Fixed-size at every scale (like ``bench_reload``'s latency table): the
    spike is a function of prompt length, so shrinking it would measure
    nothing."""
    del smoke
    max_len, chunk = 384, 16
    wave_clock, cycle_budget, long_budget = 240, 4, 6
    # the scheduler's mid-flight commit: admitted at clock C0, a pending
    # needs s = ceil((C0-1)/(chunk-1)) steps to catch the moving clock, so
    # a prompt of exactly C0+s-1 tokens completes at its own length
    long_lens = []
    c0 = wave_clock + cycle_budget
    while True:
        ln = c0 + max(1, -(-(c0 - 1) // (chunk - 1))) - 1
        if ln + long_budget > max_len:
            break
        long_lens.append(ln)
        c0 = ln + long_budget
    rng = np.random.default_rng(7)
    reqs = [Request(prompt=[int(t) for t in
                            rng.integers(1, 500, size=wave_clock)],
                    max_new_tokens=long_lens[-1] + long_budget - wave_clock,
                    request_id=i)
            for i in range(3)]
    reqs.append(Request(prompt=[int(t) for t in rng.integers(1, 500,
                                                             size=3)],
                        max_new_tokens=cycle_budget, request_id=3))
    for j, ln in enumerate(long_lens):
        reqs.append(Request(
            prompt=[int(t) for t in rng.integers(1, 500, size=ln)],
            max_new_tokens=long_budget, request_id=4 + j))
    return reqs, max_len, chunk


def bench_prefill_tail(smoke: bool = False, repeats: int = 6,
                       report=print) -> Dict:
    # always the FLOPs-bound width and the fixed-size workload — `smoke`
    # is accepted for signature parity with the other experiments but
    # changes nothing (a shrunken spike would measure nothing)
    model, params = _tail_model()
    reqs, max_len, chunk = long_prompt_workload(smoke)
    out: Dict = {"requests": len(reqs), "prefill_chunk": chunk,
                 "long_prompt_lens": [len(r.prompt) for r in reqs[4:]]}
    tokens: Dict[str, List] = {}
    clocks: Dict[str, List[int]] = {}
    for label, c in (("monolithic", 0), ("chunked", chunk)):
        eng = ServeEngine(model, params,
                          ServeConfig(max_batch=4, max_len=max_len,
                                      scheduler="continuous",
                                      prefill_chunk=c))
        eng.generate(reqs)                   # warm every jit shape
        # the schedule is deterministic, so repeated runs visit the same
        # per-step work: the elementwise min strips container stalls
        # (thread-pool hiccups) that would otherwise own the tail. GC is
        # paused outright — its pauses trigger at allocation counts, which
        # recur at the SAME step every repeat, so min-of-N can't strip them
        per_run = []
        gc.collect()
        gc.disable()
        try:
            for _ in range(repeats):
                eng.scheduler.step_log = steps = []
                outs = eng.generate(reqs)
                per_run.append([e["step_ms"] for e in steps])
        finally:
            gc.enable()
        assert len({len(r) for r in per_run}) == 1
        ms = np.asarray(per_run, np.float64).min(axis=0)
        tokens[label] = [o.tokens for o in outs]
        clocks[label] = [e["clock"] for e in eng.scheduler.admission_log
                         if e["request_id"] >= 4][-len(reqs[4:]):]
        out[label] = {
            "steps": int(ms.size),
            "p50_ms": float(np.percentile(ms, 50)),
            "p95_ms": float(np.percentile(ms, 95)),
            "p99_ms": float(np.percentile(ms, 99)),
            "max_ms": float(ms.max()),
        }
        if c:
            out[label]["chunk_steps"] = \
                eng.stats()["scheduler"]["chunk_steps"] // (repeats + 1)
        eng.close()
        m = out[label]
        report(f"[serving] prefill-tail {label:10s}: step-time p50 "
               f"{m['p50_ms']:6.2f} / p95 {m['p95_ms']:6.2f} / p99 "
               f"{m['p99_ms']:6.2f} / max {m['max_ms']:6.2f} ms "
               f"({m['steps']} steps)")
    out["tokens_identical"] = tokens["monolithic"] == tokens["chunked"]
    out["admission_clocks_identical"] = \
        clocks["monolithic"] == clocks["chunked"]
    if not out["tokens_identical"]:
        raise RuntimeError(
            "chunked prefill diverged from the monolithic path: greedy "
            f"tokens differ (admission clocks {clocks['monolithic']} vs "
            f"{clocks['chunked']}) — the equivalence guarantee is broken")
    out["p99_ratio"] = out["chunked"]["p99_ms"] / out["monolithic"]["p99_ms"]
    report(f"[serving] prefill-tail chunked/monolithic p99 ratio: "
           f"{out['p99_ratio']:.2f}x (tokens bit-identical)")
    return out


def shared_prefix_workload(smoke: bool):
    """Serial chat turns: one long shared system prompt + a short distinct
    user suffix per turn. Serving this contiguously re-prefills the system
    prompt every turn; the paged backend prefills it once and reuses its
    registered blocks for every later turn."""
    del smoke
    sys_len, turns, sfx, new = 512, 8, 8, 8
    rng = np.random.default_rng(11)
    sys_prompt = [int(t) for t in rng.integers(1, 200, size=sys_len)]
    reqs = [Request(prompt=sys_prompt
                    + [int(t) for t in rng.integers(1, 200, size=sfx)],
                    max_new_tokens=new, request_id=i)
            for i in range(turns)]
    return reqs, sys_len


def bench_shared_prefix(smoke: bool = False, repeats: int = 3,
                        report=print) -> Dict:
    """Paged-vs-contiguous on the shared-prefix workload. Both backends run
    the continuous scheduler with one slot and serve the turns serially
    (one ``generate`` per turn, the arrival pattern of a chat session), so
    each turn's prompt sits at positions ``0..L-1`` in both backends and
    greedy tokens must be bit-identical. Fixed-size at every scale (like
    ``bench_prefill_tail``), on the FLOPs-bound ``_tail_model`` width: the
    experiment measures prefill *avoidance*, and at toy widths a 500-token
    prefill is pure dispatch overhead — shrinking it would measure the
    paged backend's extra gather/scatter dispatches instead."""
    model, params = _tail_model()
    reqs, sys_len = shared_prefix_workload(smoke)
    max_len, bs = 768, 16
    ctx_len = len(reqs[0].prompt) + reqs[0].max_new_tokens
    new_tokens = sum(r.max_new_tokens for r in reqs)
    out: Dict = {"turns": len(reqs), "system_prompt_len": sys_len,
                 "context_len": ctx_len, "block_size": bs}
    tokens: Dict[str, List] = {}
    for backend in ("contiguous", "paged"):
        over = {} if backend == "contiguous" else dict(
            kv_backend="paged", block_size=bs,
            kv_blocks=2 * (max_len // bs) + 1)
        eng = ServeEngine(model, params,
                          ServeConfig(max_batch=1, max_len=max_len,
                                      scheduler="continuous", **over))

        def turns(eng=eng, reqs=reqs):
            return [eng.generate([r])[0] for r in reqs]

        turns()                 # warm every jit shape + the block registry
        kv0 = eng.scheduler.stats()["kv"]
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            outs = turns()
            best = min(best, time.perf_counter() - t0)
        kv = eng.scheduler.stats()["kv"]
        eng.close()
        tokens[backend] = [o.tokens for o in outs]
        m = {"tok_s": new_tokens / best, "wall_ms": best * 1e3,
             "turn_ms": best * 1e3 / len(reqs)}
        if backend == "paged":
            timed = repeats * len(reqs)
            m["prefix_hit_rate"] = \
                (kv["prefix_hits"] - kv0["prefix_hits"]) / timed
            m["prefix_tokens_reused_per_turn"] = \
                (kv["prefix_tokens_reused"]
                 - kv0["prefix_tokens_reused"]) / timed
            m["cow_copies"] = kv["cow_copies"]
            per_pos = kv["block_bytes"] // bs
            m["kv_bytes_per_ctx_token"] = \
                kv["peak_blocks_active"] * kv["block_bytes"] / ctx_len
        else:
            # one contiguous slot always holds max_len positions
            per_pos = None
            m["kv_bytes_per_ctx_token"] = None
        out[backend] = m
        if per_pos is not None:
            out["contiguous"]["kv_bytes_per_ctx_token"] = \
                per_pos * max_len / ctx_len
        report(f"[serving] shared-prefix {backend:10s}: "
               f"{m['tok_s']:7.0f} tok/s ({m['turn_ms']:.1f} ms/turn)")
    out["tokens_identical"] = tokens["paged"] == tokens["contiguous"]
    if not out["tokens_identical"]:
        raise RuntimeError(
            "paged backend diverged from contiguous on the shared-prefix "
            "workload: greedy tokens differ — the bit-identity guarantee "
            "is broken")
    out["ratio"] = out["paged"]["tok_s"] / out["contiguous"]["tok_s"]
    report(f"[serving] shared-prefix paged/contiguous ratio: "
           f"{out['ratio']:.2f}x (hit rate "
           f"{out['paged']['prefix_hit_rate']:.2f}, "
           f"{out['paged']['prefix_tokens_reused_per_turn']:.0f} prefix "
           f"tokens reused/turn, tokens bit-identical)")
    return out


def paged_chunked_workload(sets: int):
    """Chat-shaped mid-flight admissions on the paged backend: three
    residents decode long budgets while a cycle slot serves a sequence of
    turns that all carry the same 256-token system prefix plus a long
    distinct suffix. Once the prefix is registered, every admission pins
    its blocks and prefills only the ~288-token suffix — monolithically
    that suffix is the p99 decode step-time spike; chunked it is a bounded
    chunk per step. Suffixes are distinct per request *set* (the registry
    would otherwise absorb them after one pass and leave nothing to
    prefill) but share lengths, so every set visits the same per-step work
    and the elementwise min across sets is valid."""
    max_len, bs, chunk = 576, 16, 16
    pfx, sfx, turn_budget, turns = 256, 288, 8, 6
    rng = np.random.default_rng(13)
    prefix = [int(t) for t in rng.integers(1, 500, size=pfx)]
    residents = [Request(prompt=[int(t) for t in
                                 rng.integers(1, 500, size=64)],
                         max_new_tokens=160, request_id=i)
                 for i in range(3)]
    reqs_by_set = []
    for s in range(sets):
        reqs_by_set.append(residents + [
            Request(prompt=prefix + [int(t) for t in
                                     rng.integers(1, 500, size=sfx)],
                    max_new_tokens=turn_budget,
                    request_id=100 * s + 10 + j)
            for j in range(turns)])
    return reqs_by_set, dict(max_len=max_len, block_size=bs, chunk=chunk,
                             prefix_len=pfx, suffix_len=sfx, turns=turns)


def bench_paged_chunked(smoke: bool = False, repeats: int = 4,
                        report=print) -> Dict:
    """Paged chunked admission vs paged monolithic admission on the
    long-shared-prefix workload. Under the paged backend every pending's
    completion target is its own prompt length (no catch-up recurrence),
    so tokens are position-deterministic and must stay bit-identical for
    every chunk split (verified). Fixed-size at every scale on the
    FLOPs-bound ``_tail_model`` width, for the same reason as
    ``bench_prefill_tail``: the admission spike is a function of the
    unshared-suffix length, and shrinking it would measure nothing."""
    del smoke
    model, params = _tail_model()
    sets = repeats + 2               # 2 warm sets + `repeats` timed sets
    reqs_by_set, wl = paged_chunked_workload(sets)
    out: Dict = {"turns": wl["turns"], "system_prefix_len": wl["prefix_len"],
                 "suffix_len": wl["suffix_len"],
                 "prefill_chunk": wl["chunk"], "block_size": wl["block_size"]}
    tokens: Dict[str, List] = {}
    for label, c in (("monolithic", 0), ("chunked", wl["chunk"])):
        eng = ServeEngine(model, params,
                          ServeConfig(max_batch=4, max_len=wl["max_len"],
                                      scheduler="continuous",
                                      kv_backend="paged",
                                      block_size=wl["block_size"],
                                      kv_blocks=800, prefill_chunk=c))
        # set 0 fills the block registry (first-touch full prefills); set 1
        # warms the steady-state jit shapes (admissions now hit the
        # registered prefix, so the suffix-width forwards appear here)
        for s in range(2):
            eng.generate(reqs_by_set[s])
        kv0 = eng.scheduler.stats()["kv"]
        adm0, chunk0 = eng.scheduler.admitted, eng.scheduler.chunk_steps
        per_run: List[List[float]] = []
        toks: List[List] = []
        gc.collect()
        gc.disable()
        try:
            for s in range(2, sets):
                eng.scheduler.step_log = steps = []
                outs = eng.generate(reqs_by_set[s])
                per_run.append([e["step_ms"] for e in steps])
                toks.append([o.tokens for o in outs])
        finally:
            gc.enable()
        assert len({len(r) for r in per_run}) == 1
        ms = np.asarray(per_run, np.float64).min(axis=0)
        kv = eng.scheduler.stats()["kv"]
        timed_admits = eng.scheduler.admitted - adm0
        m = {
            "steps": int(ms.size),
            "p50_ms": float(np.percentile(ms, 50)),
            "p95_ms": float(np.percentile(ms, 95)),
            "p99_ms": float(np.percentile(ms, 99)),
            "max_ms": float(ms.max()),
            "prefix_hit_rate":
                (kv["prefix_hits"] - kv0["prefix_hits"]) / timed_admits,
        }
        if c:
            m["chunk_steps_per_set"] = \
                (eng.scheduler.chunk_steps - chunk0) // repeats
        eng.close()
        tokens[label] = toks
        out[label] = m
        report(f"[serving] paged-chunked {label:10s}: step-time p50 "
               f"{m['p50_ms']:6.2f} / p95 {m['p95_ms']:6.2f} / p99 "
               f"{m['p99_ms']:6.2f} / max {m['max_ms']:6.2f} ms "
               f"({m['steps']} steps, hit rate "
               f"{m['prefix_hit_rate']:.2f})")
    out["tokens_identical"] = tokens["monolithic"] == tokens["chunked"]
    if not out["tokens_identical"]:
        raise RuntimeError(
            "paged chunked admission diverged from the monolithic paged "
            "path: greedy tokens differ — the bit-identity guarantee is "
            "broken")
    out["p99_ratio"] = out["chunked"]["p99_ms"] / out["monolithic"]["p99_ms"]
    report(f"[serving] paged-chunked chunked/monolithic p99 ratio: "
           f"{out['p99_ratio']:.2f}x (tokens bit-identical)")
    return out


def kv_bytes_workload():
    """32 slots of distinct mid-length prompts decoding in lockstep —
    the all-residents-decoding shape where KV-cache HBM traffic owns the
    roofline. Fixed-size at every scale: the bytes-per-position ratio is
    dtype arithmetic and the agreement rate needs enough compared tokens
    (32 slots x 24 tokens = 768) for a per-mille flip rate to resolve."""
    slots, plen, new = 32, 48, 24
    rng = np.random.default_rng(21)
    reqs = [Request(prompt=[int(t) for t in rng.integers(1, 500, size=plen)],
                    max_new_tokens=new, request_id=i)
            for i in range(slots)]
    return reqs, dict(max_len=128, block_size=16, slots=slots,
                      prompt_len=plen, new_tokens=new)


def bench_kv_bytes(smoke: bool = False, repeats: int = 3,
                   report=print) -> Dict:
    """fp32 vs int8 KV pools on the 32-slot paged decode workload.

    Reports device bytes per cached position (all layers, from the live
    pool), the KV bytes a decode step reads per token (bytes/position x
    mean context length — identical contexts in both runs, so the ratio
    is exactly the dtype ratio), throughput, and the teacher-forced
    greedy-token agreement of the int8 config vs the fp oracle
    (``repro.serving.equivalence``; both engines are deterministic greedy,
    so the rate is reproducible). ``smoke`` is accepted for signature
    parity but changes nothing — see :func:`kv_bytes_workload`."""
    del smoke
    from repro.serving.equivalence import (greedy_token_agreement,
                                           oracle_tokens)
    model, params = _tail_model()
    reqs, wl = kv_bytes_workload()
    new_tokens = sum(r.max_new_tokens for r in reqs)
    # context length while decoding token t is prompt_len + t
    mean_ctx = wl["prompt_len"] + (wl["new_tokens"] - 1) / 2
    out: Dict = dict(wl, mean_context_len=mean_ctx)
    engines: Dict[str, ServeEngine] = {}
    oracle = None
    for label, quant in (("fp", False), ("int8", True)):
        eng = ServeEngine(model, params,
                          ServeConfig(max_batch=wl["slots"],
                                      max_len=wl["max_len"],
                                      max_slots=wl["slots"],
                                      scheduler="continuous",
                                      kv_backend="paged",
                                      block_size=wl["block_size"],
                                      quantize_kv=quant))
        outs = eng.generate(reqs)                # warm every jit shape
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            outs = eng.generate(reqs)
            best = min(best, time.perf_counter() - t0)
        if label == "fp":
            oracle = oracle_tokens(outs)
        kv = eng.scheduler.stats()["kv"]
        engines[label] = eng
        m = {"tok_s": new_tokens / best, "wall_ms": best * 1e3,
             "bytes_per_position": kv["bytes_per_position"],
             "kv_bytes_per_token": kv["bytes_per_position"] * mean_ctx,
             "pool_bytes": kv["pool_bytes"]}
        out[label] = m
        report(f"[serving] kv-bytes {label:5s}: {m['tok_s']:7.0f} tok/s, "
               f"{m['bytes_per_position']} B/position "
               f"({m['kv_bytes_per_token'] / 1024:.0f} KiB read/token, "
               f"pool {m['pool_bytes'] / 2**20:.1f} MiB)")
    agreement = greedy_token_agreement(engines["int8"], reqs, oracle)
    for eng in engines.values():
        eng.close()
    out["agreement"] = agreement.rate
    out["agreement_compared"] = agreement.compared
    out["bytes_ratio"] = out["int8"]["bytes_per_position"] \
        / out["fp"]["bytes_per_position"]
    out["throughput_ratio"] = out["int8"]["tok_s"] / out["fp"]["tok_s"]
    report(f"[serving] kv-bytes int8/fp: bytes {out['bytes_ratio']:.2f}x, "
           f"throughput {out['throughput_ratio']:.2f}x, greedy agreement "
           f"{out['agreement']:.4f} over {out['agreement_compared']} tokens")
    return out


# one ladder row per newly-ungated architecture feature, on reduced
# registry configs shrunk to 2 layers (agreement is a property of the
# mixer math, not the width — tiny widths keep the ladder cheap enough
# for the CI bench gate). The jamba row isolates the mamba mixer
# (moe=None, one mamba + one attention block); the moe row is mixtral
# without its window; the sliding_window+moe row measures the composed
# stack.
CHUNKED_ARCH_ROWS = (
    ("sliding_window", "granite-3-8b", dict(n_layers=2, window=8)),
    ("mla", "minicpm3-4b", dict(n_layers=2)),
    ("moe", "mixtral-8x7b", dict(n_layers=2, window=None)),
    ("mamba", "jamba-1.5-large-398b",
     dict(n_layers=2, block_pattern=("m", "a"), moe=None)),
    ("rwkv", "rwkv6-1.6b", dict(n_layers=2)),
    ("sliding_window+moe", "mixtral-8x7b", dict(n_layers=2, window=8)),
)


def chunked_archs_workload(smoke: bool):
    """One admission wave (requests == slots, so chunked and monolithic
    admission pad the batch identically and the only difference measured
    is the chunk-continuation math itself)."""
    slots = 4 if smoke else 8
    new_tokens = 8 if smoke else 12
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(slots):
        plen = int(rng.integers(5, 12))
        prompt = [int(t) for t in rng.integers(1, 200, size=plen)]
        reqs.append(Request(prompt=prompt, max_new_tokens=new_tokens,
                            request_id=i))
    return reqs, {"slots": slots, "new_tokens": new_tokens,
                  "max_len": 64, "chunks": (1, 5)}


def bench_chunked_archs(smoke: bool = False, report=print) -> Dict:
    """Per-architecture chunked-prefill agreement ladder.

    For every architecture feature that makes chunk-continuation prefill
    tolerance-equivalent rather than bit-identical (sliding-window ring
    rotation, MLA latent re-expansion, per-chunk MoE capacity routing,
    mamba/rwkv recurrent-prefix reassociation — see
    ``docs/equivalence.md``), run the chunked continuous engine against
    its own monolithic-prefill oracle and report the worst teacher-forced
    greedy agreement across chunk widths. ``agreement`` (the min) is
    gated in ``scripts/check_bench.py`` against the row's composed
    ``AGREEMENT_BUDGETS`` floor — these rows are the evidence that the
    chunked-prefill arch gates stayed lifted."""
    from repro.serving.equivalence import (agreement_budget,
                                           greedy_token_agreement,
                                           oracle_tokens)
    reqs, wl = chunked_archs_workload(smoke)
    out: Dict = dict(wl, chunks=list(wl["chunks"]), rows={})
    for label, arch, over in CHUNKED_ARCH_ROWS:
        cfg = dataclasses.replace(get_config(arch, reduced=True),
                                  dtype="float32", **over)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        base = ServeConfig(max_batch=wl["slots"], max_len=wl["max_len"],
                           scheduler="continuous")
        oracle_eng = ServeEngine(model, params, base)
        oracle = oracle_tokens(oracle_eng.generate(reqs))
        oracle_eng.close()
        by_chunk = {}
        compared = 0
        for chunk in wl["chunks"]:
            ccfg = dataclasses.replace(base, prefill_chunk=chunk)
            eng = ServeEngine(model, params, ccfg)
            rep = greedy_token_agreement(eng, reqs, oracle)
            eng.close()
            by_chunk[str(chunk)] = rep.rate
            compared = rep.compared
        budget = agreement_budget(
            dataclasses.replace(base, prefill_chunk=wl["chunks"][0]),
            model.cfg)
        row = {"arch": cfg.name,
               "features": list(model.arch_features()),
               "budget": budget,
               "agreement": min(by_chunk.values()),
               "by_chunk": by_chunk,
               "compared": compared}
        out["rows"][label] = row
        report(f"[serving] chunked {label:18s}: agreement "
               f"{row['agreement']:.4f} over {compared} tokens x "
               f"{len(by_chunk)} chunk widths (budget {budget:.3f}, "
               f"{cfg.name})")
    return out


def _spec_model():
    """A deliberately narrow LM for the speculative experiment: decode
    steps must be *dispatch/sync-bound* — the production decode regime
    (per-step latency owned by kernel launch + the per-token host logits
    sync, not FLOPs) that speculation exists to amortize. CPU fake-quant
    gives the low-bit drafter no FLOP discount, so at wider toy widths
    the draft chain's extra FLOPs swamp the step savings and the bench
    would measure the CPU artifact instead of the scheduling win."""
    cfg = get_config("granite-3-8b", reduced=True)
    cfg = dataclasses.replace(cfg, dtype="float32", n_layers=1, d_model=16,
                              n_heads=2, n_kv_heads=1, head_dim=8, d_ff=32,
                              vocab=64)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def speculative_workload():
    """Decode-heavy mixed-length requests on a small slot pool: the shape
    speculation pays for (per-token host syncs and decode dispatches
    dominate; prompts are short so admission is a small fraction of the
    run). Fixed-size at every scale — the acceptance rate and the
    steps-per-token ratio are properties of the draft/verifier pair, not
    of the model width."""
    slots, n = 4, 8
    rng = np.random.default_rng(11)
    reqs = [Request(prompt=[int(t) for t in
                            rng.integers(1, 63, size=8 + (3 * i) % 9)],
                    max_new_tokens=18 + (5 * i) % 10, request_id=i)
            for i in range(n)]
    return reqs, dict(max_len=64, block_size=8, slots=slots, draft_k=6,
                      draft_bits=6)


def bench_speculative(smoke: bool = False, repeats: int = 5,
                      report=print) -> Dict:
    """w8-only verifier decode vs w4-drafts-for-w8 self-speculative decode
    on the paged continuous scheduler (same squant-w8 serving tree; the
    speculative engine adds a squant-w4 drafter of the same checkpoint).

    Greedy acceptance promises output tokens **bit-identical** to
    verifier-only decode — asserted hard here, per request, for every
    draft config measured. Reported are decode throughput for both
    engines, the draft acceptance rate, the p50/p95 of per-slot tokens
    committed per verify cycle (1.0 == verifier-only pace), and the
    engine steps each run took (speculation's win IS steps-per-token:
    every accepted draft saves one full scheduler step — one decode
    dispatch plus one device→host logits sync).

    The headline pair runs at ``draft_bits=6``: acceptance governs
    whether the saved steps outrun the extra draft+verify compute, and
    SQuant at 4 bits on a *random-init* tiny checkpoint is a worst-case
    drafter (near-uniform logits, so low-bit argmax flips constantly —
    real trained checkpoints sit much higher). ``bits_table`` reports
    the full acceptance ladder (w4..w7 drafting for w8) so the tradeoff
    is visible rather than cherry-picked. ``smoke`` is accepted for
    signature parity but changes nothing — see
    :func:`speculative_workload` and :func:`_spec_model`."""
    del smoke
    model, params = _spec_model()
    reqs, wl = speculative_workload()
    new_tokens = sum(r.max_new_tokens for r in reqs)
    out: Dict = dict(wl, useful_tokens=new_tokens)

    def measure(spec: bool, draft_bits: int, reps: int):
        eng = ServeEngine(model, params, ServeConfig(
            max_batch=wl["slots"], max_len=wl["max_len"],
            max_slots=wl["slots"], scheduler="continuous",
            kv_backend="paged", block_size=wl["block_size"],
            quantize_weights="squant", weight_bits=8, speculative=spec,
            draft_bits=draft_bits, draft_k=wl["draft_k"]))
        outs = eng.generate(reqs)                # warm every jit shape
        steps0 = eng.stats()["scheduler"]["steps"]
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            outs = eng.generate(reqs)
            best = min(best, time.perf_counter() - t0)
        st = eng.scheduler.stats()
        m = {"tok_s": new_tokens / best, "wall_ms": best * 1e3,
             "steps_per_run": (st["steps"] - steps0) // reps}
        if spec:
            m.update(acceptance_rate=st["acceptance_rate"],
                     accepted_len=dict(st["accepted_len"]),
                     draft_tokens_proposed=st["draft_tokens_proposed"],
                     draft_tokens_accepted=st["draft_tokens_accepted"])
        eng.close()
        return m, {c.request_id: c.tokens for c in outs}

    w8, ref_tokens = measure(False, wl["draft_bits"], repeats)
    out["w8"] = w8
    report(f"[serving] w8-only    : {w8['tok_s']:7.0f} tok/s "
           f"({w8['steps_per_run']} steps/run)")
    out["bits_table"] = []
    for bits in (4, 5, 6, 7):
        headline = bits == wl["draft_bits"]
        m, toks = measure(True, bits, repeats if headline else 2)
        identical = toks == ref_tokens
        assert identical, \
            f"w{bits}-draft tokens diverged from w8-only decode"
        row = {"draft_bits": bits, "tokens_identical": identical,
               "throughput_ratio": m["tok_s"] / w8["tok_s"], **m}
        out["bits_table"].append(row)
        if headline:
            out["speculative"] = m
        report(f"[serving] w{bits}-draft   : {m['tok_s']:7.0f} tok/s "
               f"({m['steps_per_run']} steps/run, accept "
               f"{m['acceptance_rate']:.2f}, accepted-len p50 "
               f"{m['accepted_len'].get('p50', 0):.1f} p95 "
               f"{m['accepted_len'].get('p95', 0):.1f}, "
               f"{row['throughput_ratio']:.2f}x w8)")
    out["tokens_identical"] = all(r["tokens_identical"]
                                  for r in out["bits_table"])
    out["throughput_ratio"] = out["speculative"]["tok_s"] / w8["tok_s"]
    out["steps_ratio"] = out["speculative"]["steps_per_run"] \
        / max(w8["steps_per_run"], 1)
    report(f"[serving] speculative (w{wl['draft_bits']} drafts) / "
           f"w8-only: throughput {out['throughput_ratio']:.2f}x, steps "
           f"{out['steps_ratio']:.2f}x, tokens identical: "
           f"{out['tokens_identical']}")
    return out


def run(report=print, smoke: bool = False,
        out_path: str = "BENCH_serving.json") -> Dict:
    results = {"smoke": smoke,
               "throughput": bench_throughput(smoke=smoke, report=report),
               "reload": bench_reload_dip(smoke=smoke, report=report),
               "prefill_tail": bench_prefill_tail(smoke=smoke,
                                                  report=report),
               "shared_prefix": bench_shared_prefix(smoke=smoke,
                                                    report=report),
               "paged_chunked": bench_paged_chunked(smoke=smoke,
                                                    report=report),
               "kv_bytes": bench_kv_bytes(smoke=smoke, report=report),
               "chunked_archs": bench_chunked_archs(smoke=smoke,
                                                    report=report),
               "speculative": bench_speculative(smoke=smoke,
                                                report=report)}
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    report(f"[serving] wrote {out_path}")
    return results


if __name__ == "__main__":
    run(smoke="--smoke" in sys.argv)
