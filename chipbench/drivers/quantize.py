"""The whole seeded fp tree quantized again and again by ``quantize_tree``.

Set-up: weights from the seed (one jitted call, resident for the run) and
``warm_calls`` whole quantizations (the first compiles; the first calls of
a process are slow even with every program cached). The window then
quantizes the whole tree call after call, dropping each call's output
before the next, and ends at the end of the first whole tree after
``--seconds``: ``quantize_s`` is the window over the trees quantized.

Afterwards the last tree's codes and scales are compared, on rows drawn
from the seed in every quantized leaf, with a plain SQuant of the same
seeded weights (``reference/squant.py``): the stored bytes (packed for 4
bits) and the scales.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import harness
import weights
from reference import squant as ref_squant
from trace import Tracer


def _quantized(tree) -> List[tuple]:
    """``(names, QuantizedTensor)`` of every quantized leaf."""
    from repro.quant.qtypes import QuantizedTensor
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    return [(weights.path_names(kp), x) for kp, x in flat
            if isinstance(x, QuantizedTensor)]


def compare(params, out, q: Dict[str, Any], rows_per_leaf: int, seed: int,
            control: bool) -> Dict[str, float]:
    """Worst leaf's share of differing stored code bytes, and the largest
    relative scale error, over sampled rows of every quantized leaf."""
    src = {weights.path_names(kp): x for kp, x in
           jax.tree_util.tree_flatten_with_path(params)[0]}
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 11])
    bits, g = int(q["bits"]), q["group_size"]
    worst = scale_err = 0.0
    ctl_worst = ctl_scale = 0.0
    n_bytes = n_leaves = 0
    for names, qt in _quantized(out):
        w = src[names]                                  # (in, out)
        m = w.shape[1]
        rows = np.sort(rng.choice(m, size=min(rows_per_leaf, m),
                                  replace=False))
        w2 = np.asarray(w[:, rows].T.astype(jnp.float32))  # (R, in)
        eff = None if (g is None or g >= w2.shape[1]) else int(g)
        c, s = ref_squant.codes(w2, bits, eff)
        want = ref_squant.pack(c, bits)
        got = np.asarray(qt.data[rows])
        got_s = np.asarray(qt.scale.reshape(qt.scale.shape[0], -1)[rows])
        worst = max(worst, float(np.mean(got != want)))
        scale_err = max(scale_err, float(np.max(np.abs(got_s - s) / s)))
        n_bytes += want.size
        n_leaves += 1
        if control:
            lo = w2.astype(jnp.float8_e4m3fn).astype(np.float32)
            cc, cs = ref_squant.codes(lo, bits, eff)
            ctl_worst = max(ctl_worst, float(np.mean(
                ref_squant.pack(cc, bits) != want)))
            ctl_scale = max(ctl_scale, float(np.max(np.abs(cs - s) / s)))
    out_ = {"code_mismatch_max": worst, "scale_rel_err_max": scale_err,
            "bytes_compared": n_bytes, "leaves_compared": n_leaves}
    if control:
        out_["control_code_mismatch_max"] = ctl_worst
        out_["control_scale_rel_err_max"] = ctl_scale
    return out_


def tree_work(params, q: Dict[str, Any], peaks) -> Dict[str, Any]:
    """Least bytes of one whole quantization: each quantized weight read
    once, its codes and scales written once; the share that the
    ``squant_flip`` kernel does (grouped rows) is counted apart."""
    from repro.core.pipeline import is_quantizable
    sq = harness.load_module("kernels", "squant_flip")
    bits, g = int(q["bits"]), q["group_size"]
    tot_b = ker_b = 0.0
    for kp, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        names = weights.path_names(kp)
        if not is_quantizable(names, x):
            continue
        n_in, m = x.shape[-2], x.shape[-1]
        _, b = sq.cost(m, n_in, bits, w_bytes=x.dtype.itemsize)
        tot_b += b
        if g is not None and g < n_in:
            ker_b += b
    return {"squant_flip": {"flops": 0.0, "bytes": ker_b},
            "tree_least_s": tot_b / peaks["hbm_bytes_per_s"]}


def run(ctx: harness.Context) -> harness.Record:
    from repro.core.pipeline import quantize_tree
    from repro.models.model import build_model

    ov = ctx.overrides
    cfg = harness.program_config(ctx.config, ov.get("arch"))
    q = dict(ctx.cell["quantize"], **ov.get("quantize", {}))
    chk = dict(ctx.cell["check"], **ov.get("check", {}))
    model = build_model(dataclasses.replace(cfg, scan_layers=False))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make_params(shapes, ctx.seed, jnp.dtype(cfg.dtype))
    kw = dict(method=q["method"], bits=int(q["bits"]),
              group_size=q["group_size"], backend=q["backend"])
    quant = ov.get("quantize_fn", quantize_tree)
    for i in range(int(q["warm_calls"])):
        out, rep = quant(params, **kw)
        del out
        harness.log(f"warm quantize {i}: {rep.total_millis:.0f} ms, at "
                    f"{time.perf_counter() - ctx.t_start:.1f} s")

    tracer = Tracer(ctx.trace_dir) if ctx.trace else None
    if tracer is not None:
        tracer.start()
    t_open = time.perf_counter()
    reports = []
    while True:
        with jax.profiler.TraceAnnotation("chipbench.quantize_tree"):
            out, rep = quant(params, **kw)
        reports.append(rep)
        t_close = time.perf_counter()
        if t_close - t_open >= ctx.seconds:
            break
        del out
    if tracer is not None:
        tracer.stop()
    devices = jax.devices()
    mem = harness.peak_memory_bytes(devices[:1])
    window_s = t_close - t_open
    e2e = {"quantize_s": window_s / len(reports),
           "setup_s": t_open - ctx.t_start}
    peaks = harness.peaks_for(devices[0].device_kind) \
        if devices[0].platform == "tpu" else ov.get("peaks")
    layer: Dict[str, Any] = {
        "kind": "quantize", "window_s": window_s, "trees": len(reports),
        "window_t": (t_open, t_close)}
    if peaks:
        layer["peaks"] = peaks
        layer["work"] = tree_work(params, q, peaks)

    trace = None
    if tracer is not None:
        trace = tracer.reduce({"squant_flip": harness.load_module(
            "kernels", "squant_flip").PATTERNS})

    harness.log(f"window {window_s:.2f} s, {len(reports)} trees; last "
                f"{reports[-1].summary()}")
    t_ref = time.perf_counter()
    res = compare(params, out, q, int(chk["rows_per_leaf"]), ctx.seed,
                  ctx.control)
    harness.log(f"reference over {res['bytes_compared']} code bytes of "
                f"{res['leaves_compared']} leaves: "
                f"{time.perf_counter() - t_ref:.1f} s")
    del out, params
    gc.collect()
    lim = chk["limits"]
    checks = [harness.Check("code_mismatch_max", res["code_mismatch_max"],
                            float(lim["code_mismatch_max"])),
              harness.Check("scale_rel_err_max", res["scale_rel_err_max"],
                            float(lim["scale_rel_err_max"]))]
    extra = {"bytes_compared": res["bytes_compared"]}
    if ctx.control:
        extra["control"] = {
            "code_mismatch_max": res["control_code_mismatch_max"],
            "scale_rel_err_max": res["control_scale_rel_err_max"]}
    return harness.Record(e2e=e2e, checks=checks, attempted=res[
        "leaves_compared"], failed=0, memory_peak_bytes=mem, layer=layer,
        trace=trace, extra=extra)
