"""Table 3 analog: quantization wall-time.

The paper's headline systems claim: SQuant quantizes whole networks in
milliseconds (no data, no BP) while generative DFQ takes minutes-hours.
Here: SQuant vs data-free AdaRound (ZeroQ-style synthesis + gradient
rounding) on the toy CNN, plus per-layer SQuant timing on mid-size LM
weight matrices (up to granite-3-8b-sized layers), plus the serial
(per-layer sync) vs batched (bucketed, one sync) pipeline comparison —
run as a script it writes the batched-pipeline numbers to
``BENCH_pipeline.json``.
"""
from __future__ import annotations

import json
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import quantize_tree
from repro.core.squant import SQuantConfig, squant
from repro.quant.qtypes import QuantizedTensor

from _toy import train_cnn
from bench_accuracy import quantize_cnn


def _tree_codes(tree):
    leaves = jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    return [np.asarray(l.codes()) for l in leaves
            if isinstance(l, QuantizedTensor)]


def bench_pipeline(report=print) -> Dict:
    """Serial per-layer loop vs batched bucketed vs sharded pipeline.

    Toy CNN + one reduced LM; asserts all paths emit identical int8 codes.
    The sharded column row-partitions each bucket over a 1-axis 'data' mesh
    spanning every host device (1 on the plain CPU container; run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for a real
    multi-device measurement). Returns a ``BENCH_pipeline.json``-compatible
    dict.
    """
    from repro.configs import get_config
    from repro.launch.mesh import make_quantize_mesh
    from repro.models.model import build_model

    out: Dict = {}
    mesh = make_quantize_mesh()
    out["pipeline_mesh_devices"] = int(mesh.size)
    modes = {"serial": {"batched": False},
             "batched": {},
             "sharded": {"mesh": mesh}}
    cnn_params, _, _ = train_cnn(steps=10)
    lm_cfg = get_config("granite-3-8b", reduced=True)
    lm_params = build_model(lm_cfg).init(jax.random.PRNGKey(0))

    reps = 7
    for name, params in (("cnn", cnn_params), ("lm", lm_params)):
        times = {mode: float("inf") for mode in modes}
        trees = {}
        for mode, kw in modes.items():                    # warm the jit cache
            quantize_tree(params, method="squant", bits=4, **kw)
        for _ in range(reps):       # interleave modes so machine drift cancels
            for mode, kw in modes.items():
                t0 = time.perf_counter()
                trees[mode], rep = quantize_tree(params, method="squant",
                                                 bits=4, **kw)
                ms = (time.perf_counter() - t0) * 1e3
                if ms < times[mode]:
                    times[mode] = ms
                    if mode == "batched":
                        out[f"pipeline_{name}_buckets"] = len(rep.buckets)
                        out[f"pipeline_{name}_layers"] = len(rep.layers)
        for mode in modes:
            out[f"pipeline_{name}_{mode}_ms"] = times[mode]
        base = _tree_codes(trees["serial"])
        identical = all(
            np.array_equal(a, b)
            for mode in ("batched", "sharded")
            for a, b in zip(base, _tree_codes(trees[mode])))
        out[f"pipeline_{name}_codes_identical"] = bool(identical)
        out[f"pipeline_{name}_speedup"] = times["serial"] / max(
            times["batched"], 1e-9)
        report(f"pipeline,{name},serial_ms={times['serial']:.1f},"
               f"batched_ms={times['batched']:.1f},"
               f"sharded_ms={times['sharded']:.1f},"
               f"speedup={out[f'pipeline_{name}_speedup']:.2f}x,"
               f"identical={identical}")
    return out


def run(report=print) -> Dict:
    out = {}
    params, bn, _ = train_cnn(steps=60)   # quality irrelevant here

    # whole-network quantization time (second call = steady-state, jitted)
    for method in ("rtn", "squant"):
        quantize_tree(params, method=method, bits=4, dequantize=True)
        t0 = time.perf_counter()
        _, rep = quantize_tree(params, method=method, bits=4,
                               dequantize=True)
        ms = (time.perf_counter() - t0) * 1e3
        out[f"cnn_{method}_ms"] = ms
        report(f"table3,cnn,{method},total_ms={ms:.1f},"
               f"layers={len(rep.layers)}")

    t0 = time.perf_counter()
    quantize_cnn(params, bn, "adaround_df", 4)
    ms = (time.perf_counter() - t0) * 1e3
    out["cnn_adaround_df_ms"] = ms
    report(f"table3,cnn,adaround_df,total_ms={ms:.1f},layers=5")

    # per-layer SQuant timing at LM-layer scale (steady-state, jitted)
    rng = np.random.default_rng(0)
    for (m, n) in ((1024, 1024), (4096, 4096), (4096, 12800)):
        w = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
        cfg = SQuantConfig(bits=4, group_size=128)
        qt, _ = squant(w, cfg)                      # compile
        jax.block_until_ready(qt.data)
        t0 = time.perf_counter()
        for _ in range(3):
            qt, _ = squant(w, cfg)
            jax.block_until_ready(qt.data)
        ms = (time.perf_counter() - t0) / 3 * 1e3
        out[f"layer_{m}x{n}_ms"] = ms
        report(f"table3,layer,{m}x{n},squant_ms={ms:.2f}")

    out.update(bench_pipeline(report))
    return out


if __name__ == "__main__":
    res = run()
    pipe = {k: v for k, v in res.items() if k.startswith("pipeline_")}
    with open("BENCH_pipeline.json", "w") as f:
        json.dump(pipe, f, indent=1)
    print(f"wrote BENCH_pipeline.json ({len(pipe)} metrics)")
