"""A saturated queue served by the continuous scheduler over the paged KV
cache, with SQuant codes behind every linear.

Set-up: weights from the seed (one jitted call), ``ServeEngine`` (which
quantizes them), a warm-up job with one request of every prompt length
the traffic uses (so every chunk width and block-scatter shape has been
compiled), then one seeded job larger than the window can serve. The
window opens once the slot pool has been full and a request has retired,
lasts ``--seconds``, and is closed from the ``on_step`` hook, which ends
the job. Only tokens that reached the host inside the window count.

Afterwards: the peak memory is read, the engine is freed, and a sample of
the requests that finished in the window (the longest among them) is
compared with a plain float32 forward of the same seeded weights
(``reference/gqa.py``): the widest gap by which a served token's
reference logit lies below the reference's best.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import harness
import traffic
import weights
from reference import gqa
from trace import Tracer


class WindowClosed(Exception):
    """Raised from ``on_step`` to end the job when the window closes."""


LINEAR_NAMES = {"attn": ("wq", "wk", "wv", "wo"),
                "ffn": ("wi", "wg", "wdown")}


class Window:
    """The ``on_step`` hook: opens, records and closes the window.

    Per step it notes the time, the tokens delivered, the chunk and
    admission times, the slots that finished (their tokens are then
    complete) and the context each live slot decodes over. Slots are read
    from ``engine.scheduler.slots`` (request and tokens of each slot)."""

    def __init__(self, eng, seconds: float, max_slots: int,
                 tracer: Optional[Tracer], max_fill_steps: int):
        self.eng = eng
        self.seconds = seconds
        self.max_slots = max_slots
        self.tracer = tracer
        self.max_fill_steps = max_fill_steps
        self.prev: List[Any] = [None] * max_slots
        self.full = False
        self.retired = 0
        self.fill_steps = 0
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.t_last: Optional[float] = None
        # per window step: t, recorded, chunk_ms, admit_ms, live context
        # positions, live slots after the step, prompt tokens admitted
        self.rows: List[tuple] = []
        self.finished: List[tuple] = []      # (request, tokens) in window

    def _scan(self):
        fin, admitted, ctx, live = [], 0, 0, 0
        slots = self.eng.scheduler.slots
        for i, s in enumerate(slots):
            p = self.prev[i]
            if p is not None and p is not s:
                fin.append(p)
            if s is not None:
                if s is not p:
                    admitted += len(s.req.prompt)
                ctx += len(s.req.prompt) + len(s.tokens)
                live += 1
        self.prev = list(slots)
        return fin, admitted, ctx, live

    def on_step(self, info: Dict[str, Any]) -> None:
        t = info["t"]
        rec = info["recorded"]
        fin, admitted, ctx, live = self._scan()
        if self.t_open is None:
            self.fill_steps += 1
            self.full |= rec >= self.max_slots
            self.retired += len(fin)
            if (self.full and self.retired) \
                    or self.fill_steps >= self.max_fill_steps:
                if self.tracer is not None:
                    self.tracer.start()
                self.traces_open = dict(self.eng.trace_counts)
                self.t_open = self.t_last = time.perf_counter()
            return
        self.rows.append((t, rec, info.get("chunk_ms", 0.0),
                          info.get("admit_ms", 0.0), ctx, live, admitted,
                          t - self.t_last))
        self.t_last = t
        self.finished.extend((s.req, list(s.tokens)) for s in fin)
        if t - self.t_open >= self.seconds:
            self.t_close = t
            self.retraced = {k: v - self.traces_open.get(k, 0) for k, v in
                             self.eng.trace_counts.items()
                             if v != self.traces_open.get(k, 0)}
            if self.tracer is not None:
                self.tracer.stop()
            raise WindowClosed()


def weighted_quantile(values, weights_, q: float) -> float:
    v = np.asarray(values, np.float64)
    w = np.asarray(weights_, np.float64)
    order = np.argsort(v, kind="stable")
    v, w = v[order], w[order]
    cw = np.cumsum(w)
    return float(v[np.searchsorted(cw, q * cw[-1], side="left")])


def _linear_shapes(cfg) -> List[tuple]:
    """(out, in) of every quantized linear of one block."""
    d, hd = cfg.d_model, cfg.head_dim
    return [(cfg.n_heads * hd, d), (cfg.n_kv_heads * hd, d),
            (cfg.n_kv_heads * hd, d), (d, cfg.n_heads * hd),
            (cfg.d_ff, d), (cfg.d_ff, d), (d, cfg.d_ff)]


def window_work(cfg, rows: List[tuple], bits: int, peaks) -> Dict[str, Any]:
    """Logical work of the window from the step records: per kernel its
    operations and bytes, and the least time of every program launch
    (decode steps and prefill chunks, which run one after the other)."""
    dm = harness.load_module("kernels", "dequant_matmul")
    pa = harness.load_module("kernels", "paged_attention")
    L, hd = cfg.n_layers, cfg.head_dim
    shapes = _linear_shapes(cfg)
    bf, bw = peaks["bf16_flops"], peaks["hbm_bytes_per_s"]
    emb_bytes = cfg.vocab * cfg.d_model * 2
    kv_pos_bytes = 2 * cfg.n_kv_heads * hd * 2 * L
    live_rows = sum(r[5] for r in rows)         # decode rows that carry work
    chunk_calls = sum(1 for r in rows if r[2] > 0)
    prompt_tok = sum(r[6] for r in rows)
    positions = sum(r[4] for r in rows)
    dq_f = dq_b = 0.0
    for m, n in shapes:
        f, b = dm.cost(live_rows, m, n, bits, calls=len(rows))
        dq_f, dq_b = dq_f + L * f, dq_b + L * b
        if chunk_calls:
            f, b = dm.cost(prompt_tok, m, n, bits, calls=chunk_calls)
            dq_f, dq_b = dq_f + L * f, dq_b + L * b
    pa_f, pa_b = pa.cost(positions * L, live_rows * L, cfg.n_heads,
                         cfg.n_kv_heads, hd)
    # least time of each launch: decode steps, then prefill chunks
    least = 0.0
    lin_params = sum(m * n for m, n in shapes) * L
    w_bytes = sum(m * n * bits / 8 + 4 * m for m, n in shapes) * L
    for r in rows:
        f = 2.0 * r[5] * (lin_params + cfg.vocab * cfg.d_model) \
            + 4.0 * r[4] * cfg.n_heads * hd * L
        b = w_bytes + emb_bytes + r[4] * kv_pos_bytes
        least += max(f / bf, b / bw)
    if chunk_calls:
        # a prompt of p tokens attends causally: p (p + 1) / 2 pairs
        pairs = sum(r[6] ** 2 / 2.0 for r in rows)
        f = 2.0 * prompt_tok * lin_params \
            + 4.0 * pairs * cfg.n_heads * hd * L \
            + 2.0 * chunk_calls * cfg.vocab * cfg.d_model
        b = chunk_calls * (w_bytes + emb_bytes) + prompt_tok * kv_pos_bytes
        least += max(f / bf, b / bw)
    return {"dequant_matmul": {"flops": dq_f, "bytes": dq_b},
            "paged_attention": {"flops": pa_f, "bytes": pa_b},
            "least_s": least}


def _sample(finished, k: int, seed: int) -> List[tuple]:
    """The longest finished request and ``k - 1`` others drawn from the
    seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0].prompt)
                                   + len(finished[i][1])))
    rest = order[1:]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 7])
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False)) \
        if rest else []
    return [finished[i] for i in [order[0]] + pick]


def reference_gaps(cfg, shapes, seed: int, sample, control: bool,
                   eps: float, max_len: int, max_new: int) -> Dict[str, float]:
    """Widest gap of the served tokens in the float32 reference (and, with
    ``control``, of the tokens a 4-bit-weight reference would pick).
    Shapes are fixed by the cell (``max_len`` positions, ``max_new``
    served tokens per request), so the reference compiles once."""
    get = weights.leaf_maker(shapes, seed, jnp.dtype(cfg.dtype))

    def f32(names):
        return get(names).astype(jnp.float32)

    def layer(l):
        pre = ("stack", "list", str(l), "b0")
        p = {"ln1": f32(pre + ("ln1", "gain")),
             "ln2": f32(pre + ("ln2", "gain"))}
        for grp, names in LINEAR_NAMES.items():
            for n in names:
                p[n] = f32(pre + (grp, n, "w"))
        return p

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
    rcfg = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                norm_eps=eps)
    emb = f32(("embedding", "embedding"))
    fin = f32(("final_norm", "gain"))
    pos, served, valid = map(jnp.asarray, (pos, served, valid))
    x = gqa.forward_hidden(tokens, emb, layer, cfg.n_layers, rcfg)
    ref = gqa.logits_at(x, pos, fin, emb, eps=eps)
    del x
    out = {"logit_gap_max": float(gqa.gaps(ref, served, valid).max()),
           "tokens_compared": int(valid.sum())}
    if control:
        x = gqa.forward_hidden(tokens, emb, layer, cfg.n_layers, rcfg,
                               linear_bits=4)
        low = gqa.logits_at(x, pos, fin, emb, eps=eps).argmax(-1)
        out["control_logit_gap_max"] = float(
            gqa.gaps(ref, low, valid).max())
    return out


def run(ctx: harness.Context) -> harness.Record:
    from repro.models.model import build_model
    from repro.serving.engine import Request, ServeConfig, ServeEngine

    ov = ctx.overrides
    cfg = harness.program_config(ctx.config, ov.get("arch"))
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
        kv_blocks=sv["kv_blocks"])
    eng = ServeEngine(model, weights.make_params(
        shapes, ctx.seed, jnp.dtype(cfg.dtype)), scfg)
    for fault in ov.get("faults", ()):
        fault(eng)
    quant_report = eng.quant_report
    harness.log(f"engine built at {time.perf_counter() - ctx.t_start:.1f} s "
                f"(quantize {quant_report.total_millis:.0f} ms)")

    # warm-up. The first decode step adds the block tables and per-slot
    # positions to the pool's arrays, so one short request decodes first;
    # then every prompt length of the traffic is admitted once (each chunk
    # width and each block-scatter size, against the pool as decode leaves
    # it), with one new token each so that no further decode step runs
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
    except WindowClosed:
        pass
    devices = jax.devices()
    mem = harness.peak_memory_bytes(devices[:1])

    rows = win.rows
    window_s = win.t_close - win.t_open
    toks = sum(r[1] for r in rows)
    gaps_ms = [r[7] * 1e3 for r in rows]
    e2e = {"output_tokens_per_s": toks / window_s,
           "itl_p95_ms": weighted_quantile(gaps_ms, [r[1] for r in rows],
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
    g = sorted((r[7] for r in rows), reverse=True)
    harness.log(f"step gaps: longest {[round(x, 3) for x in g[:5]]} s; "
                f"{sum(1 for r in rows if r[2] > 0)} chunk steps")

    trace = None
    if tracer is not None:
        trace = tracer.reduce({k: harness.load_module("kernels", k).PATTERNS
                               for k in ("dequant_matmul", "paged_attention")})

    harness.log(f"traffic: {sum(len(r.prompt) for r, _ in finished)} prompt "
                f"and {sum(len(t) for _, t in finished)} output tokens "
                f"finished in the window")
    sample = _sample(finished, int(chk["sample_requests"]), ctx.seed)
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
