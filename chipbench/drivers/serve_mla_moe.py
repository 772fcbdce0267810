"""``serve_offline``'s saturated queue, for a stack of latent attention
(MLA) and routed experts: one chip's expert-parallel share of the model.

The same set-up, warm-up, window and sample as ``serve_offline`` (its
``Window``, ``weighted_quantile`` and ``_sample``), with these
differences:

* the configuration file's MLA and MoE keys are checked against the
  program's configuration (``harness.program_config`` checks the keys all
  configurations share), and the program is given the file's expert
  share: ``n_routed_experts`` held of the router's ``router_width``;
* the router's selection bias is drawn from the seed (:func:`score_biases`)
  for the program and the reference alike, where the generic seeding
  would give it a constant;
* the paged pool is donated to the programs that write it
  (``ServeConfig.donate_kv``): two pools of this size do not fit;
* each window row also carries the step's ``positions_attended``,
  ``positions_walked``, ``moe_pairs_here`` and ``moe_rows_computed`` (row
  indices 8-11), which ``kv_walk_useful_share`` and
  ``moe_rows_useful_share`` read;
* the window's work counts the latent cache and the experts
  (:func:`window_work`);
* ``correct`` compares with ``reference/mla_moe.py``.
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
import traffic
import weights
from reference import mla_moe
from trace import Tracer

so = harness.load_module("drivers", "serve_offline")
COUNTERS = ("positions_attended", "positions_walked", "moe_pairs_here",
            "moe_rows_computed")


class Window(so.Window):
    """``serve_offline.Window`` with the step's counters appended to its
    row (indices 8-11; 0 where the program does not report one)."""

    def on_step(self, info: Dict[str, Any]) -> None:
        n = len(self.rows)
        try:
            super().on_step(info)
        finally:
            if len(self.rows) > n:
                self.rows[-1] = self.rows[-1] + tuple(
                    int(info.get(k, 0)) for k in COUNTERS)


def share_config(conf: Dict[str, Any]):
    """The program's configuration for the file, with the file's expert
    share, checked against every MLA and MoE key the file states."""
    cfg = harness.program_config(conf)
    m, e = cfg.mla, cfg.moe
    if m is None or e is None:
        raise ValueError(f"{conf['name']}: the program's {conf['arch']} has "
                         f"no latent attention or no experts")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        e, n_held=int(conf["n_routed_experts"]), first_held=0))
    e = cfg.moe
    want = {"kv_lora_rank": m.kv_lora, "q_lora_rank": m.q_lora,
            "qk_nope_head_dim": m.nope_dim, "qk_rope_head_dim": m.rope_dim,
            "v_head_dim": m.v_dim, "first_k_dense_replace": cfg.first_k_dense,
            "moe_intermediate_size": e.d_expert,
            "n_shared_experts": e.n_shared, "num_experts_per_tok": e.top_k,
            "router_width": e.n_experts, "moe_layer_freq": e.every,
            "scoring_func": e.scoring, "norm_topk_prob": e.norm_topk,
            "routed_scaling_factor": e.routed_scale,
            # one expert group: the program selects over all experts
            "n_group": 1, "topk_group": 1}
    for key, have in want.items():
        if conf.get(key) != have:
            raise ValueError(f"{conf['name']}: {key}={conf.get(key)!r} but "
                             f"the program's {conf['arch']} has {have!r}")
    if conf["reduced"]["n_routed_experts"][0] != e.n_experts:
        raise ValueError(f"{conf['name']}: the source's expert count is not "
                         f"the router's width {e.n_experts}")
    return cfg


BIAS_STD = 0.1   # about half the spread of the sigmoid scores (std 0.2)


def score_biases(shapes, seed: int, dtype) -> Dict[tuple, Any]:
    """Each router's selection bias (leaves named ``score_bias``), normal
    with ``BIAS_STD`` from ``(seed, leaf index)`` and rounded to the served
    dtype: the choice then rests on score plus a bias that differs by
    expert, as in the source."""
    key = jax.random.fold_in(weights.base_key(seed), 0x5B1A5)
    return {nm: (BIAS_STD * jax.random.normal(
                jax.random.fold_in(key, i), s.shape, jnp.float32)
                 ).astype(dtype)
            for i, (nm, s) in enumerate(weights.leaf_table(shapes))
            if nm[-1] == "score_bias"}


def _with_biases(params, biases: Dict[tuple, Any]):
    return jax.tree_util.tree_map_with_path(
        lambda kp, x: biases.get(weights.path_names(kp), x), params)


def _linears(cfg) -> Dict[str, tuple]:
    """(out, in) of each linear that runs through ``dequant_matmul``, with
    the layers it is in: the attention projections of every layer, the
    shared experts and the dense FFN, and the head. ``kv_up`` runs there
    only in prefill, where it expands the latent rows."""
    m, e, d = cfg.mla, cfg.moe, cfg.d_model
    L, dense = cfg.n_layers, cfg.first_k_dense
    sh = e.n_shared * e.d_expert
    return {"wq": ((cfg.n_heads * (m.nope_dim + m.rope_dim), d), L),
            "kv_down": ((m.kv_lora + m.rope_dim, d), L),
            "wo": ((d, cfg.n_heads * m.v_dim), L),
            "shared_in": ((sh, d), 2 * (L - dense)),
            "shared_down": ((d, sh), L - dense),
            "dense_in": ((cfg.d_ff, d), 2 * dense),
            "dense_down": ((d, cfg.d_ff), dense),
            "head": ((cfg.vocab, d), 1)}


def latent_attention_cost(positions: int, rows: int, n_heads: int,
                          width: int, v_width: int, act_bytes: int = 2):
    """(operations, bytes) of absorbed paged decode: ``rows`` queries of
    ``n_heads`` heads over ``positions`` latent rows of ``width`` lanes,
    scores over all of them and values over the first ``v_width``; each
    latent row read once, queries in and outputs back."""
    flops = 2.0 * positions * n_heads * (width + v_width)
    return flops, positions * width * act_bytes \
        + rows * n_heads * (width + v_width) * act_bytes


def window_work(cfg, rows: List[tuple], bits: int, peaks) -> Dict[str, Any]:
    """Logical work of the window from the step records: per kernel its
    operations and bytes, and the least time of every program launch
    (decode steps, then prefill chunks). Routed experts count the token-
    expert pairs they serve (``moe_pairs_here``), at the bytes of the held
    banks once per launch."""
    dm = harness.load_module("kernels", "dequant_matmul")
    m, e = cfg.mla, cfg.moe
    L, d, h = cfg.n_layers, cfg.d_model, cfg.n_heads
    lat_w = m.kv_lora + m.rope_dim
    moe_layers = L - cfg.first_k_dense
    bf, bw = peaks["bf16_flops"], peaks["hbm_bytes_per_s"]
    lin = _linears(cfg)
    kv_up = (h * (m.nope_dim + m.v_dim), m.kv_lora)
    live_rows = sum(r[5] for r in rows)
    chunk_calls = sum(1 for r in rows if r[2] > 0)
    prompt_tok = sum(r[6] for r in rows)
    positions = sum(r[4] for r in rows)
    dq_f = dq_b = 0.0
    for (mo, n), k in lin.values():
        f, b = dm.cost(live_rows, mo, n, bits, calls=len(rows))
        dq_f, dq_b = dq_f + k * f, dq_b + k * b
        if chunk_calls:
            f, b = dm.cost(prompt_tok, mo, n, bits, calls=chunk_calls)
            dq_f, dq_b = dq_f + k * f, dq_b + k * b
    if chunk_calls:
        f, b = dm.cost(prompt_tok, *kv_up, bits, calls=chunk_calls)
        dq_f, dq_b = dq_f + L * f, dq_b + L * b
    pa_f, pa_b = latent_attention_cost(positions * L, live_rows * L, h,
                                       lat_w, m.kv_lora)
    # least time of each launch
    expert = 3 * d * e.d_expert                      # parameters of one
    dense_params = sum(mo * n * k for (mo, n), k in lin.values())
    w_bytes = sum((mo * n * bits / 8 + 4 * mo) * k
                  for (mo, n), k in lin.values()) \
        + L * (kv_up[0] * kv_up[1] * bits / 8 + 4 * kv_up[0]) \
        + moe_layers * e.held * (expert * bits / 8 + 4 * 2 * e.d_expert
                                 + 4 * d) \
        + moe_layers * d * e.n_experts * 2           # bf16 routers
    absorb = 2.0 * h * m.kv_lora * (m.nope_dim + m.v_dim) * L
    least = 0.0
    for r in rows:
        pairs = r[10] if len(r) > 10 else 0
        f = 2.0 * r[5] * dense_params + 2.0 * pairs * expert \
            + r[5] * absorb + 2.0 * r[4] * h * (lat_w + m.kv_lora) * L
        b = w_bytes + r[4] * lat_w * 2 * L
        least += max(f / bf, b / bw)
    if chunk_calls:
        # a prompt of p tokens attends causally: p (p + 1) / 2 pairs, keys
        # of nope + rope lanes and values of v lanes per head
        pairs_qk = sum(r[6] ** 2 / 2.0 for r in rows)
        f = 2.0 * prompt_tok * (dense_params + L * kv_up[0] * kv_up[1]) \
            + 2.0 * pairs_qk * h * (m.nope_dim + m.rope_dim + m.v_dim) * L
        b = chunk_calls * w_bytes + prompt_tok * lat_w * 2 * L
        least += max(f / bf, b / bw)
    return {"dequant_matmul": {"flops": dq_f, "bytes": dq_b},
            "paged_attention": {"flops": pa_f, "bytes": pa_b},
            "least_s": least}


def _layer_getter(shapes, get):
    """``layer(l)``: block ``l``'s float32 weights as nested dicts, and
    whether it has experts (leading dense layers under ``stack/lead``,
    the others under ``stack/list/<i>/b0``)."""
    table = weights.leaf_table(shapes)
    lead = sum(1 for nm, _ in table if nm[:2] == ("stack", "lead")
               and nm[3:] == ("ln1", "gain"))

    def layer(l):
        pre = ("stack", "lead", str(l)) if l < lead else \
            ("stack", "list", str(l - lead), "b0")
        p: Dict[str, Any] = {}
        for nm, _ in table:
            if nm[:len(pre)] == pre:
                node = p
                for k in nm[len(pre):-1]:
                    node = node.setdefault(k, {})
                node[nm[-1]] = get(nm).astype(jnp.float32)
        return p, "moe" in p
    return layer


def reference_gaps(cfg, shapes, seed: int, sample, control: bool,
                   eps: float, max_len: int, max_new: int) -> Dict[str, float]:
    """Widest gap of the served tokens in the float32 reference (and, with
    ``control``, of the tokens a 4-bit-weight reference would pick).
    Shapes are fixed by the cell (``max_len`` positions, ``max_new``
    served tokens per request), so the reference compiles once."""
    leaf = weights.leaf_maker(shapes, seed, jnp.dtype(cfg.dtype))
    biases = score_biases(shapes, seed, jnp.dtype(cfg.dtype))

    def get(names):
        names = tuple(names)
        return biases[names] if names in biases else leaf(names)
    layer = _layer_getter(shapes, get)
    b = len(sample)
    tokens = np.zeros((b, max_len), np.int32)
    pos = np.zeros((b, max_new), np.int32)
    served = np.zeros((b, max_new), np.int32)
    valid = np.zeros((b, max_new), bool)
    for i, (r, toks) in enumerate(sample):
        seq = list(r.prompt) + toks[:-1]
        tokens[i, :len(seq)] = seq
        n = len(toks)
        pos[i, :n] = np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + n)
        served[i, :n] = toks
        valid[i, :n] = True
    m, e = cfg.mla, cfg.moe
    rcfg = dict(n_heads=cfg.n_heads, nope=m.nope_dim, rope=m.rope_dim,
                v=m.v_dim, kv_lora=m.kv_lora, theta=cfg.rope_theta, eps=eps,
                top_k=e.top_k, routed_scale=e.routed_scale,
                first_held=e.first_held)
    emb = get(("embedding", "embedding")).astype(jnp.float32)
    fin = get(("final_norm", "gain")).astype(jnp.float32)
    head = get(("lm_head", "w")).astype(jnp.float32)
    pos, served, valid = map(jnp.asarray, (pos, served, valid))
    x = mla_moe.forward_hidden(tokens, emb, layer, cfg.n_layers, rcfg)
    ref = mla_moe.logits_at(x, pos, fin, head, eps=eps)
    del x
    out = {"logit_gap_max": float(mla_moe.gaps(ref, served, valid).max()),
           "tokens_compared": int(valid.sum())}
    if control:
        x = mla_moe.forward_hidden(tokens, emb, layer, cfg.n_layers, rcfg,
                                   linear_bits=4)
        low = mla_moe.logits_at(x, pos, fin, mla_moe.round_linear(head, 4),
                                eps=eps).argmax(-1)
        out["control_logit_gap_max"] = float(
            mla_moe.gaps(ref, low, valid).max())
    return out


def run(ctx: harness.Context) -> harness.Record:
    from repro.models.model import build_model
    from repro.serving.engine import Request, ServeConfig, ServeEngine

    ov = ctx.overrides
    cfg = share_config(ctx.config)
    if ov.get("arch"):
        cfg = dataclasses.replace(cfg, **ov["arch"])
    sv = dict(ctx.cell["serve"], **ov.get("serve", {}))
    tr = dict(ctx.cell["traffic"], **ov.get("traffic", {}))
    chk = dict(ctx.cell["check"], **ov.get("check", {}))
    model = build_model(dataclasses.replace(cfg, scan_layers=False))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    scfg = ServeConfig(
        max_batch=sv["max_slots"], max_len=sv["max_len"],
        quantize_weights="squant", weight_bits=sv["weight_bits"],
        dequantize_for_compute=False, scheduler="continuous",
        max_slots=sv["max_slots"], prefill_chunk=sv["prefill_chunk"],
        kv_backend="paged", block_size=sv["block_size"],
        kv_blocks=sv["kv_blocks"], donate_kv=True)
    dt = jnp.dtype(cfg.dtype)
    eng = ServeEngine(model, _with_biases(
        weights.make_params(shapes, ctx.seed, dt),
        score_biases(shapes, ctx.seed, dt)), scfg)
    for fault in ov.get("faults", ()):
        fault(eng)
    harness.log(f"engine built at {time.perf_counter() - ctx.t_start:.1f} s "
                f"(quantize {eng.quant_report.total_millis:.0f} ms)")

    # warm-up, as serve_offline: one short request that decodes, then one
    # request of every prompt length of the traffic with one new token
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 1])
    lens = traffic.distinct_prompt_lengths(tr)
    eng.generate([Request(prompt=rng.integers(0, cfg.vocab, lens[0]).tolist(),
                          max_new_tokens=2, request_id=1 << 30)])
    warm = [Request(prompt=rng.integers(0, cfg.vocab, n).tolist(),
                    max_new_tokens=1, request_id=(1 << 30) + 1 + i)
            for i, n in enumerate(lens)]
    eng.generate(warm)
    harness.log(f"warm-up done at {time.perf_counter() - ctx.t_start:.1f} s "
                f"({len(warm)} prompt lengths)")

    reqs = [Request(prompt=p, max_new_tokens=o, request_id=i)
            for i, (p, o) in enumerate(traffic.job(tr, ctx.seed, cfg.vocab))]
    tracer = Tracer(ctx.trace_dir) if ctx.trace else None
    win = Window(eng, ctx.seconds, sv["max_slots"], tracer,
                 int(sv.get("max_fill_steps", 4096)))
    eng.on_step = win.on_step
    try:
        eng.generate(reqs)
        raise RuntimeError("the job ended before the window closed: the "
                           "cell's traffic needs more requests")
    except so.WindowClosed:
        pass
    devices = jax.devices()
    mem = harness.peak_memory_bytes(devices[:1])

    rows = win.rows
    window_s = win.t_close - win.t_open
    toks = sum(r[1] for r in rows)
    gaps_ms = [r[7] * 1e3 for r in rows]
    e2e = {"output_tokens_per_s": toks / window_s,
           "itl_p95_ms": so.weighted_quantile(gaps_ms, [r[1] for r in rows],
                                              0.95),
           "setup_s": win.t_open - ctx.t_start}
    peaks = harness.peaks_for(devices[0].device_kind) \
        if devices[0].platform == "tpu" else ov.get("peaks")
    layer = {"kind": "serve", "window_s": window_s, "rows": rows,
             "window_t": (win.t_open, win.t_close),
             "traced_in_window": win.retraced,
             "max_slots": sv["max_slots"]}
    if peaks:
        layer["peaks"] = peaks
        layer["work"] = window_work(cfg, rows, sv["weight_bits"], peaks)
    finished = win.finished
    failed = sum(1 for r, t in finished
                 if len(t) != r.max_new_tokens
                 or any(not 0 <= x < cfg.vocab for x in t))

    # free the program's state before the reference runs
    eng.on_step = None
    win.eng = None
    eng.close()
    win_fill = win.fill_steps
    harness.log(f"engine programs traced inside the window: {win.retraced}")
    del eng, reqs, warm, win
    gc.collect()
    harness.log(f"window {window_s:.2f} s, {len(rows)} steps, {toks} tokens, "
                f"{win_fill} fill steps; {len(finished)} finished; device "
                f"bytes in use after freeing the engine: "
                f"{harness.bytes_in_use(devices[:1])}")
    harness.log("step gaps in ms (tokens, chunk or not): " + " ".join(
        f"{r[7] * 1e3:.1f}({r[1]}{'c' if r[2] > 0 else ''})" for r in rows))
    harness.log(f"live slots per window step: first {rows[0][5]}, mean "
                f"{np.mean([r[5] for r in rows]):.1f}, last {rows[-1][5]}"
                if rows else "no window steps")
    harness.log(f"counters over the window: "
                f"{dict(zip(COUNTERS, np.sum([r[8:12] for r in rows], 0).tolist()))}"
                if rows else "no window steps")

    trace = None
    if tracer is not None:
        trace = tracer.reduce({k: harness.load_module("kernels", k).PATTERNS
                               for k in ("dequant_matmul", "paged_attention")})

    sample = so._sample(finished, int(chk["sample_requests"]), ctx.seed)
    checks: List[harness.Check] = []
    extra: Dict[str, Any] = {}
    if sample:
        t_ref = time.perf_counter()
        eps = float(ctx.config["departures"]["rms_norm_eps"]["run"])
        gaps = reference_gaps(cfg, shapes, ctx.seed, sample, ctx.control,
                              eps, sv["max_len"], int(tr["output"]["max"]))
        harness.log(f"reference over {gaps['tokens_compared']} tokens of "
                    f"{len(sample)} requests: "
                    f"{time.perf_counter() - t_ref:.1f} s")
        checks.append(harness.Check("logit_gap_max", gaps["logit_gap_max"],
                                    float(chk["limits"]["logit_gap_max"])))
        extra["tokens_compared"] = gaps["tokens_compared"]
        if ctx.control:
            extra["control"] = {
                "logit_gap_max": gaps["control_logit_gap_max"]}
    return harness.Record(e2e=e2e, checks=checks, attempted=len(finished),
                          failed=failed, memory_peak_bytes=mem, layer=layer,
                          trace=trace, extra=extra)
