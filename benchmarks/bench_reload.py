"""Hot-reload benchmark: quantize → stage → swap latency and the
decode-throughput dip a live reload inflicts on serving.

SQuant's pitch is that data-free quantization is cheap enough to run *on*
the serving device between decode rounds. This measures exactly that, via
the versioned ``WeightStore``:

* **staging latency** — wall time for ``stage(fp_params)`` (the batched
  ``quantize_tree`` path) and for a native quantized-checkpoint restore
  (``stage(serving_params)``), on the toy CNN and the reduced LM;
* **swap latency** — the round-boundary ``acquire()`` pointer flip;
* **throughput dip** — decode tokens/s per round on the reduced LM while a
  background reload quantizes + stages concurrently, vs the undisturbed
  baseline.

Writes ``BENCH_reload.json``.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict

import jax
import numpy as np

from repro.configs import get_config
from repro.core.pipeline import quantize_tree
from repro.models.model import build_model
from repro.serving.engine import Request, ServeConfig, ServeEngine
from repro.serving.weights import WeightStore, unstacked

from _toy import init_cnn


def _reduced_lm():
    cfg = get_config("granite-3-8b", reduced=True)
    cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def bench_stage_latency(report=print) -> Dict:
    """quantize→stage→swap wall time per workload and source format."""
    out: Dict = {}
    lm_model, lm_params = _reduced_lm()
    workloads = {
        "toy_cnn": (init_cnn(jax.random.PRNGKey(0)), None),
        "reduced_lm": (lm_params, lm_model),
    }
    for name, (params, model) in workloads.items():
        def quantize_fn(tree):
            return quantize_tree(tree, method="squant", bits=8,
                                 dequantize=True)

        store = WeightStore(quantize_fn, fp_params=params)
        t0 = time.perf_counter()
        store.stage(fp_params=params, source="bench", block=True)
        stage_fp_ms = (time.perf_counter() - t0) * 1e3
        _, swap_ms = store.acquire()

        qtree, qrep = quantize_tree(unstacked(params, model and model.cfg),
                                    method="squant", bits=8)
        t0 = time.perf_counter()
        store.stage(serving_params=qtree, source="bench-native", block=True)
        stage_native_ms = (time.perf_counter() - t0) * 1e3
        _, swap2_ms = store.acquire()
        store.close()
        out[name] = {"stage_fp_quantize_ms": stage_fp_ms,
                     "stage_native_quantized_ms": stage_native_ms,
                     "quantize_only_ms": qrep.total_millis,
                     "swap_ms": max(swap_ms, swap2_ms)}
        report(f"[reload] {name}: stage(fp→squant w8) {stage_fp_ms:.1f} ms, "
               f"stage(native codes) {stage_native_ms:.1f} ms, "
               f"swap {max(swap_ms, swap2_ms):.3f} ms")
    return out


def bench_throughput_dip(rounds: int = 10, reload_round: int = 4,
                         max_new: int = 16, report=print) -> Dict:
    """Decode-throughput per round on the reduced LM; a background reload
    (quantize+stage of a fresh fp tree) starts at ``reload_round``."""
    model, params = _reduced_lm()
    _, params2 = _reduced_lm()
    eng = ServeEngine(model, params,
                      ServeConfig(max_batch=4, max_len=64,
                                  quantize_weights="squant", weight_bits=8))
    reqs = [Request(prompt=[1, 2, 3, 4], max_new_tokens=max_new,
                    request_id=i) for i in range(4)]
    eng.generate(reqs)                                  # warm the jit cache
    base_version = eng.store.version
    tok_s, swap_ms = [], []
    for r in range(rounds):
        if r == reload_round:
            eng.store.stage(fp_params=params2, source="bench-reload")
        outs = eng.generate(reqs)
        toks = sum(len(o.tokens) for o in outs)
        dec_ms = outs[0].decode_ms
        tok_s.append(toks / (dec_ms / 1e3))
        swap_ms.append(outs[0].swap_ms)
    # normally the reload already swapped in mid-run; if staging outlasted
    # the measured rounds, wait for it and swap so the stats below describe
    # the reloaded version
    assert eng.store.wait_staged(version=base_version, timeout=120), \
        "reload never staged"
    eng.store.acquire()
    eng.close()
    log = eng.stats()["round_log"][1:]                  # skip warmup entry
    baseline = float(np.median(tok_s[:reload_round]))
    during = tok_s[reload_round:]
    dip_pct = 100.0 * (1.0 - min(during) / baseline)
    staged_ms = eng.store.current.staged_ms
    out = {"rounds": rounds, "reload_round": reload_round,
           "decode_tok_s": tok_s,
           "baseline_tok_s": baseline,
           "min_tok_s_during_reload": float(min(during)),
           "dip_pct": dip_pct,
           "staged_ms": staged_ms,
           "swap_ms": swap_ms,
           "versions": [e["version"] for e in log],
           "final_version": eng.store.version}
    report(f"[reload] LM decode: baseline {baseline:.0f} tok/s, during "
           f"reload min {min(during):.0f} tok/s (dip {dip_pct:.1f}%), "
           f"staged in {staged_ms:.0f} ms, final v{eng.store.version}")
    return out


def run(report=print) -> Dict:
    results = {"stage_latency": bench_stage_latency(report=report),
               "throughput_dip": bench_throughput_dip(report=report)}
    with open("BENCH_reload.json", "w") as f:
        json.dump(results, f, indent=1)
    report("[reload] wrote BENCH_reload.json")
    return results


if __name__ == "__main__":
    run()
