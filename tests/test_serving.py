"""Serving engine tests: batched generation, on-the-fly quantized serving,
int8 KV caches, multi-round batching."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build_model
from repro.serving.engine import Request, ServeConfig, ServeEngine


def _model(arch="granite-3-8b", **over):
    cfg = get_config(arch, reduced=True)
    cfg = dataclasses.replace(cfg, dtype="float32", **over)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, cfg


def test_batched_generation_shapes():
    model, params, cfg = _model()
    eng = ServeEngine(model, params, ServeConfig(max_batch=4, max_len=64))
    reqs = [Request(prompt=[1, 2, 3], max_new_tokens=5, request_id=i)
            for i in range(6)]                      # forces two rounds
    outs = eng.generate(reqs)
    assert len(outs) == 6
    assert all(len(o.tokens) == 5 for o in outs)
    assert all(0 <= t < cfg.vocab for o in outs for t in o.tokens)


def test_greedy_deterministic():
    model, params, _ = _model()
    eng = ServeEngine(model, params, ServeConfig(max_batch=2, max_len=64))
    r = [Request(prompt=[5, 6, 7], max_new_tokens=8)]
    a = eng.generate(r)[0].tokens
    b = eng.generate(r)[0].tokens
    assert a == b


def test_quantized_serving_w8_close_to_fp():
    """Teacher-forced logit deltas under w8 SQuant stay far below the logit
    scale (free-running greedy on an untrained model diverges at near-ties,
    so the comparison is per-step)."""
    model, params, _ = _model()
    q8 = ServeEngine(model, params,
                     ServeConfig(max_batch=2, max_len=64,
                                 quantize_weights="squant", weight_bits=8))
    assert q8.quant_report is not None and q8.quant_report.layers
    batch = {"tokens": jnp.asarray([[5, 6, 7, 9, 2]], jnp.int32)}
    c1, c2 = model.init_cache(1, 16), model.init_cache(1, 16)
    l1, c1 = model.prefill(params, batch, c1)
    l2, c2 = model.prefill(q8.params, batch, c2)
    scale = float(np.abs(np.asarray(l1)).max())
    assert float(np.abs(np.asarray(l1) - np.asarray(l2)).max()) < 0.05 * scale
    for t in (3, 1, 4):
        tok = jnp.asarray([[t]], jnp.int32)
        l1, c1 = model.decode_step(params, tok, c1)
        l2, c2 = model.decode_step(q8.params, tok, c2)
        assert float(np.abs(np.asarray(l1) - np.asarray(l2)).max()) \
            < 0.05 * scale


def test_quantized_serving_methods_run():
    model, params, _ = _model()
    for method in ("rtn", "squant", "squant_ek"):
        eng = ServeEngine(model, params,
                          ServeConfig(max_batch=2, max_len=48,
                                      quantize_weights=method,
                                      weight_bits=4))
        outs = eng.generate([Request(prompt=[1, 2], max_new_tokens=4)])
        assert len(outs[0].tokens) == 4


def test_int8_kv_cache_close_to_fp():
    """Teacher-forced decode with int8 KV tracks the fp cache closely."""
    model, params, _ = _model()
    batch = {"tokens": jnp.asarray([[9, 8, 7, 6]], jnp.int32)}
    c1 = model.init_cache(1, 16, quantize_kv=False)
    c2 = model.init_cache(1, 16, quantize_kv=True)
    l1, c1 = model.prefill(params, batch, c1)
    l2, c2 = model.prefill(params, batch, c2)
    scale = float(np.abs(np.asarray(l1)).max())
    for t in (3, 1, 4, 1):
        tok = jnp.asarray([[t]], jnp.int32)
        l1, c1 = model.decode_step(params, tok, c1)
        l2, c2 = model.decode_step(params, tok, c2)
        assert float(np.abs(np.asarray(l1) - np.asarray(l2)).max()) \
            < 0.08 * scale


def test_moe_and_rwkv_serving():
    for arch in ("mixtral-8x7b", "rwkv6-1.6b"):
        model, params, _ = _model(arch)
        eng = ServeEngine(model, params,
                          ServeConfig(max_batch=2, max_len=48))
        outs = eng.generate([Request(prompt=[3, 1, 4], max_new_tokens=4)])
        assert len(outs[0].tokens) == 4


def test_quantized_expert_serving():
    """QuantizedTensor expert banks serve without dequantize_for_compute."""
    model, params, _ = _model("mixtral-8x7b")
    eng = ServeEngine(model, params,
                      ServeConfig(max_batch=2, max_len=48,
                                  quantize_weights="squant", weight_bits=8,
                                  dequantize_for_compute=False))
    outs = eng.generate([Request(prompt=[3, 1, 4], max_new_tokens=3)])
    assert len(outs[0].tokens) == 3


def test_build_engine_real_codes_layout_and_dtype():
    """``launch.serve.build_engine`` — the construction the serve CLI and
    the chip smoke test share — initialises the fp tree in the layout the
    engine serves (unrolled for real int codes, so staging makes no
    unstacked copy) and in the config's dtype, and serves from it."""
    from repro.launch.serve import build_arch_model, build_engine, \
        init_params, max_len_for
    from repro.quant.qtypes import QuantizedTensor
    model = build_arch_model("granite-3-8b", reduced=True)
    prompts = [[1, 2, 3], list(range(1, 21))]
    assert max_len_for(prompts, 4, 16) == 32
    eng = build_engine(model, ServeConfig(
        max_batch=2, max_len=max_len_for(prompts, 4, 16),
        quantize_weights="squant", weight_bits=8,
        dequantize_for_compute=False))
    assert "list" in eng.params["stack"]
    leaves = jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    assert any(isinstance(x, QuantizedTensor) for x in leaves)
    outs = eng.generate([Request(prompt=p, max_new_tokens=4, request_id=i)
                         for i, p in enumerate(prompts)])
    assert [len(o.tokens) for o in outs] == [4, 4]
    eng.close()
    bf16 = build_model(dataclasses.replace(model.cfg, dtype="bfloat16"))
    dtypes = {a.dtype for a in jax.tree_util.tree_leaves(init_params(bf16))}
    assert dtypes == {jnp.dtype(jnp.bfloat16)}


def test_serve_cli_fails_on_reload_error(tmp_path, monkeypatch, capsys):
    """A reload the weight store rejects (here a w4 checkpoint under a w8
    serve) makes the serve CLI exit non-zero, after printing the error."""
    import sys
    from repro.checkpoint.checkpointer import Checkpointer, quant_tree_meta
    from repro.launch import serve
    from repro.serving.weights import make_weight_pipeline
    model = serve.build_arch_model("granite-3-8b", reduced=True)
    qtree, report = make_weight_pipeline(model, ServeConfig(
        quantize_weights="squant", weight_bits=4,
        dequantize_for_compute=False))[1](serve.init_params(model))
    meta = quant_tree_meta(4, "squant", 128, report=report)
    Checkpointer(str(tmp_path), async_save=False).save_serving(
        1, qtree, quant_meta=meta)
    # keeps main() from pointing this process's compile cache at the repo
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    monkeypatch.setattr(sys, "argv", [
        "serve", "--quantize", "squant", "--bits", "8", "--batch", "1",
        "--max-new", "2", "--prompts", "hi", "--rounds", "2",
        "--reload-from", str(tmp_path), "--reload-poll", "0.01"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code not in (0, None)
    assert "reload error" in capsys.readouterr().out


def test_compile_cache_dir(monkeypatch):
    """The entry points' cache helper defers to JAX_COMPILATION_CACHE_DIR
    when it is set and otherwise fixes the cache at <repo>/.jax_cache."""
    import os
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
