"""Quantized checkpoint tests: native save/restore of ``QuantizedTensor``
serving trees (int4 kept packed on disk), quant metadata validation,
torn-save semantics, reshard-on-restore bit-exactness across 1/2/8-device
meshes (quantize → save → restore on a different mesh size → serve must
produce codes, scales, and generated tokens identical to the
never-checkpointed in-memory path), and reloads that serve what
quantize-on-load serves.
"""
import dataclasses
import json
import os
import textwrap

import jax
import numpy as np
import pytest

from repro.checkpoint.checkpointer import (Checkpointer, CheckpointMetaError,
                                          quant_tree_meta)
from repro.configs import get_config
from repro.models.model import build_model
from repro.quant.qtypes import is_quantized_tree
from repro.serving.engine import ServeConfig
from repro.serving.weights import make_weight_pipeline


def _tiny(seed=0):
    cfg = get_config("granite-3-8b", reduced=True)
    cfg = dataclasses.replace(cfg, dtype="float32", n_layers=2, d_model=32,
                              n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
                              vocab=64)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed)), cfg


def _quantized(model, params, bits):
    """``(tree, quant_meta)``: what a real-code w``bits`` engine serves."""
    _, quantize_fn, _ = make_weight_pipeline(model, ServeConfig(
        quantize_weights="squant", weight_bits=bits,
        dequantize_for_compute=False))
    tree, report = quantize_fn(params)
    return tree, quant_tree_meta(bits, "squant", 128, report=report)


def _assert_trees_equal(a, b):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(fa) == len(fb)
    for (k1, x), (k2, y) in zip(fa, fb):
        assert jax.tree_util.keystr(k1) == jax.tree_util.keystr(k2)
        assert x.dtype == y.dtype, jax.tree_util.keystr(k1)
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            jax.tree_util.keystr(k1)


@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_roundtrip_bit_exact(tmp_path, bits):
    model, params, _ = _tiny()
    qtree, meta = _quantized(model, params, bits)
    assert is_quantized_tree(qtree) and not is_quantized_tree(params)
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save_serving(3, qtree, quant_meta=meta)
    restored, m, step = ck.restore_serving(
        expect={"quantize_weights": "squant", "weight_bits": bits})
    assert step == 3 and m["format"] == "quantized"
    assert m["quant"]["bits"] == bits and m["quant"]["method"] == "squant"
    assert m["quant"]["packed_int4"] == (bits <= 4)
    _assert_trees_equal(qtree, restored)


def test_int4_nibbles_stay_packed_on_disk(tmp_path):
    model, params, _ = _tiny()
    qtree, meta = _quantized(model, params, 4)
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save_serving(1, qtree, quant_meta=meta)
    data = np.load(str(tmp_path / "step_00000001" / "shard_00000.npz"))
    qtensors = ck.read_meta(1)["qtensors"]
    assert qtensors, "no packed int4 payload on disk"
    for k, q in qtensors.items():
        codes = data[f"params::{k}/data"]
        scale = data[f"params::{k}/scale"]
        assert q["bits"] == 4 and codes.dtype == np.int8  # two nibbles/byte
        # packed column count is half the logical in-dim
        assert codes.shape == (q["shape"][0], q["shape"][1] // 2)
        assert codes.shape[-2] == scale.shape[-2]


def test_restore_rejects_bits_and_method_mismatch(tmp_path):
    model, params, _ = _tiny()
    qtree, meta = _quantized(model, params, 4)
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save_serving(1, qtree, quant_meta=meta)
    with pytest.raises(CheckpointMetaError, match="mismatch"):
        ck.restore_serving(expect={"quantize_weights": "squant",
                                   "weight_bits": 8})
    with pytest.raises(CheckpointMetaError, match="mismatch"):
        ck.restore_serving(expect={"quantize_weights": "rtn",
                                   "weight_bits": 4})
    with pytest.raises(CheckpointMetaError, match="unquantized"):
        ck.restore_serving(expect={"quantize_weights": None,
                                   "weight_bits": 8})
    # matching expectations (or none at all) load fine
    ck.restore_serving(expect={"quantize_weights": "squant",
                               "weight_bits": 4})
    ck.restore_serving()


def test_training_restore_rejects_quantized_checkpoint(tmp_path):
    model, params, _ = _tiny()
    qtree, meta = _quantized(model, params, 8)
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save_serving(1, qtree, quant_meta=meta)
    with pytest.raises(CheckpointMetaError, match="restore_serving"):
        ck.restore(1, template=(params, {}))


def test_save_serving_requires_bits_and_method(tmp_path):
    model, params, _ = _tiny()
    qtree, _ = _quantized(model, params, 8)
    ck = Checkpointer(str(tmp_path), async_save=False)
    with pytest.raises(ValueError, match="quant_meta missing"):
        ck.save_serving(1, qtree, quant_meta={"bits": 8})


@pytest.mark.parametrize("kind", ["fp", "quantized"])
def test_restore_skips_torn_and_corrupt_steps(tmp_path, kind):
    model, params, _ = _tiny(0)
    _, params2, _ = _tiny(1)
    ck = Checkpointer(str(tmp_path), async_save=False)

    def save(step, tree):
        if kind == "fp":
            ck.save_serving(step, tree)
        else:
            q, m = _quantized(model, tree, 8)
            ck.save_serving(step, q, quant_meta=m)

    save(1, params)
    save(2, params2)
    save(3, params2)
    os.remove(str(tmp_path / "step_00000002" / "COMMITTED"))     # torn
    with open(str(tmp_path / "step_00000003" / "index.json"), "w") as f:
        f.write('{"trees": ')                                    # corrupt
    assert ck.list_steps() == [1]
    _, meta, step = ck.restore_serving()
    assert step == 1
    with pytest.raises(CheckpointMetaError, match="COMMITTED"):
        ck.read_meta(2)
    with pytest.raises(CheckpointMetaError, match="index.json"):
        ck.read_meta(3)


def test_gc_removes_invalid_step_dirs(tmp_path):
    """Torn/corrupt step dirs are invisible to restore but must still be
    garbage-collected, or they leak one model-sized dir per occurrence."""
    model, params, _ = _tiny()
    ck = Checkpointer(str(tmp_path), async_save=False, keep=2)
    ck.save_serving(1, params)
    ck.save_serving(2, params)
    os.remove(str(tmp_path / "step_00000001" / "COMMITTED"))     # now torn
    ck.save_serving(3, params)                                   # runs _gc
    assert not (tmp_path / "step_00000001").exists()
    assert ck.list_steps() == [2, 3]


def test_quant_tree_meta_report_digest():
    from repro.core.pipeline import quantize_tree
    model, params, _ = _tiny()
    _, report = quantize_tree(params, method="squant", bits=8,
                              dequantize=True)
    meta = quant_tree_meta(8, "squant", 128, report=report)
    assert meta["report"]["layers"] == len(report.layers)
    assert meta["report"]["backend"] == report.backend
    json.dumps(meta)                                 # index.json-safe


# ---------------------------------------------------------------------------
# Reshard-on-restore: 1/2/8 virtual devices, bit-exact + token-identical
# ---------------------------------------------------------------------------

_RESHARD_SCRIPT = textwrap.dedent("""
    import dataclasses, tempfile
    import jax, numpy as np
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.checkpoint.checkpointer import Checkpointer, quant_tree_meta
    from repro.launch.mesh import make_quantize_mesh
    from repro.serving.engine import Request, ServeConfig, ServeEngine
    from repro.serving.weights import WeightStore, make_weight_pipeline

    assert len(jax.devices()) == {devices}
    cfg = dataclasses.replace(get_config("granite-3-8b", reduced=True),
                              dtype="float32", n_layers=2, d_model=32,
                              n_heads=2, n_kv_heads=1, head_dim=16,
                              d_ff=64, vocab=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(max_batch=2, max_len=32, quantize_weights="squant",
                       weight_bits={bits}, dequantize_for_compute=False)
    qtree, report = make_weight_pipeline(model, scfg)[1](params)
    meta = quant_tree_meta({bits}, "squant", 128, report=report)
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp, async_save=False)
        ck.save_serving(1, qtree, quant_meta=meta)
        restored, m, _ = ck.restore_serving(
            expect={{"quantize_weights": "squant", "weight_bits": {bits}}},
            mesh=make_quantize_mesh())
    fa = jax.tree_util.tree_flatten_with_path(qtree)[0]
    fb = jax.tree_util.tree_flatten_with_path(restored)[0]
    assert len(fa) == len(fb)
    assert (jax.tree_util.tree_structure(qtree)
            == jax.tree_util.tree_structure(restored))
    for (k1, a), (k2, b) in zip(fa, fb):
        assert jax.tree_util.keystr(k1) == jax.tree_util.keystr(k2)
        assert np.array_equal(np.asarray(a), np.asarray(b)), \\
            jax.tree_util.keystr(k1)

    def toks(tree):
        eng = ServeEngine(model, cfg=scfg,
                          store=WeightStore(serving_params=tree))
        return [c.tokens for c in eng.generate(
            [Request(prompt=[1, 2, 3], max_new_tokens=6, request_id=i)
             for i in range(2)])]

    assert toks(qtree) == toks(restored)
    print("RESHARD_OK", {devices}, {bits})
""")


@pytest.mark.parametrize("devices,bits", [(1, 4), (2, 4), (8, 4), (2, 8)])
def test_reshard_on_restore_multidevice(multidevice_run, devices, bits):
    """Quantize → save → restore onto a different mesh size → serve:
    codes/scales bit-exact and greedy tokens identical to the in-memory
    tree (the checkpoint stores full logical arrays; device_put re-splits
    them)."""
    out = multidevice_run(_RESHARD_SCRIPT.format(devices=devices,
                                                 bits=bits),
                          devices=devices)
    assert f"RESHARD_OK {devices} {bits}" in out


def test_reshard_on_restore_inprocess(tmp_path):
    """Same contract on the real in-process device set — CI's multidevice
    lane (8 virtual devices) and its 2-device reshard step run this against
    genuinely sharded restores; single-device runs cover the trivial
    mesh."""
    from repro.launch.mesh import make_quantize_mesh
    from repro.serving.engine import Request, ServeConfig, ServeEngine
    from repro.serving.weights import WeightStore

    model, params, _ = _tiny()
    qtree, meta = _quantized(model, params, 4)
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save_serving(1, qtree, quant_meta=meta)
    restored, _, _ = ck.restore_serving(
        expect={"quantize_weights": "squant", "weight_bits": 4},
        mesh=make_quantize_mesh())
    _assert_trees_equal(qtree, restored)

    def toks(tree):
        eng = ServeEngine(model, cfg=ServeConfig(
            max_batch=2, max_len=32, quantize_weights="squant",
            weight_bits=4, dequantize_for_compute=False),
            store=WeightStore(serving_params=tree))
        return [c.tokens for c in eng.generate(
            [Request(prompt=[1, 2, 3], max_new_tokens=6, request_id=i)
             for i in range(2)])]

    assert toks(qtree) == toks(restored)


# ---------------------------------------------------------------------------
# A quantized checkpoint serves what quantize-on-load serves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,bits,fake_quant", [
    ("granite-3-8b", 8, False), ("granite-3-8b", 4, False),
    ("mixtral-8x7b", 8, False), ("mixtral-8x7b", 4, False),
    ("moonshot-v1-16b-a3b", 8, False), ("moonshot-v1-16b-a3b", 4, False),
    ("granite-3-8b", 8, True), ("granite-3-8b", 4, True)])
def test_reloaded_checkpoint_serves_like_quantize_on_load(
        tmp_path, monkeypatch, arch, bits, fake_quant):
    """``launch.quantize --serving-ckpt`` writes a quantized checkpoint
    from the seed-0 fp tree; an engine started on other weights stages it
    through ``WeightStore.poll_checkpoints`` and serves the same tokens as
    an engine that quantizes the seed-0 tree on load."""
    import sys
    from repro.launch import quantize, serve
    from repro.serving.engine import Request, ServeEngine

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    monkeypatch.setattr(sys, "argv", [
        "quantize", "--arch", arch, "--reduced", "--bits", str(bits),
        "--out", str(tmp_path / "fq"), "--serving-ckpt", str(tmp_path / "sq"),
        "--serving-step", "7"])
    quantize.main()
    cfg = get_config(arch, reduced=True)
    model = build_model(dataclasses.replace(cfg, dtype="float32"))
    scfg = ServeConfig(max_batch=2, max_len=32, quantize_weights="squant",
                       weight_bits=bits, dequantize_for_compute=fake_quant)
    reqs = [Request(prompt=[(7 * i + 3 * j) % cfg.vocab for j in range(5)],
                    max_new_tokens=6, request_id=i) for i in range(2)]

    on_load = ServeEngine(model, serve.init_params(model, seed=0), scfg)
    want = [c.tokens for c in on_load.generate(reqs)]
    on_load.close()
    eng = ServeEngine(model, serve.init_params(model, seed=1), scfg)
    ck = Checkpointer(str(tmp_path / "sq"), async_save=False)
    assert eng.store.poll_checkpoints(
        ck, expect={"quantize_weights": "squant", "weight_bits": bits}) == 7
    got = eng.generate(reqs)
    eng.close()
    assert all(c.weights_version == 2 for c in got)
    assert [c.tokens for c in got] == want
