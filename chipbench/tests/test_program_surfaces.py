"""The program's fields that the serving window reads besides its public
surfaces: ``engine.scheduler.slots`` (each slot's ``req`` and ``tokens``),
``engine.trace_counts`` and ``engine.quant_report``. A refactor that moves
them breaks these tests before it silently changes what the benchmark
measures."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import harness
import weights
from conftest import TINY_ARCH


def _engine(on_step=None):
    from repro.models.model import build_model
    from repro.serving.engine import ServeConfig, ServeEngine
    man = harness.load_manifest()
    conf = {c["name"]: c for c in man["configs"]}["granite-3-8b-pp2"]
    cfg = harness.program_config(harness.load_json(harness.ROOT
                                                   / conf["file"]), TINY_ARCH)
    model = build_model(dataclasses.replace(cfg, scan_layers=False))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    scfg = ServeConfig(
        max_batch=3, max_len=128, quantize_weights="squant", weight_bits=8,
        dequantize_for_compute=False, scheduler="continuous", max_slots=3,
        prefill_chunk=16, kv_backend="paged", block_size=16)
    eng = ServeEngine(model, weights.make_params(shapes, 3,
                                                 jnp.dtype(cfg.dtype)), scfg)
    return eng, cfg


def test_slots_carry_request_and_tokens_and_window_reads_them():
    from repro.serving.engine import Request
    drv = harness.load_module("drivers", "serve_offline")
    eng, cfg = _engine()
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, n).tolist(),
                    max_new_tokens=m, request_id=i)
            for i, (n, m) in enumerate([(20, 5), (40, 3), (9, 8), (33, 6),
                                        (17, 4), (50, 7)])]
    seen = []

    def look(info):
        assert {"t", "recorded", "chunk_ms", "admit_ms"} <= set(info)
        slots = eng.scheduler.slots
        assert len(slots) == 3
        for s in slots:
            if s is not None:
                assert s.req in reqs
                assert all(isinstance(t, int) for t in s.tokens)
                seen.append(s.req.request_id)
        win.on_step(info)

    win = drv.Window(eng, 1e9, 3, None, max_fill_steps=1)
    eng.on_step = look
    done = {c.request_id: list(c.tokens) for c in eng.generate(reqs)}
    assert set(seen) == set(done)
    # every request the window saw retire carries its whole completion
    assert len(win.finished) >= len(reqs) - 3
    for r, toks in win.finished:
        assert toks == done[r.request_id] and len(toks) == r.max_new_tokens
    # live contexts and admitted prompt tokens come from the same slots
    assert sum(r[6] for r in win.rows) > 0 and all(r[4] >= 0
                                                   for r in win.rows)


def test_trace_counts_and_quant_report():
    eng, _ = _engine()
    assert isinstance(eng.trace_counts, dict)
    assert all(isinstance(v, int) for v in eng.trace_counts.values())
    assert eng.quant_report.total_millis > 0
