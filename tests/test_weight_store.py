"""Versioned weight store + zero-downtime reload tests.

Covers: version/swap semantics (swaps land ONLY at decode-round
boundaries — a version staged mid-round never tears the in-flight round),
background staging (latest request wins), the checkpoint watcher (fp
checkpoints re-quantized on the fly, quantized checkpoints loaded natively,
torn/corrupt step dirs skipped, metadata mismatches rejected), and a live
multi-round reload with zero failed requests.
"""
import dataclasses
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint.checkpointer import Checkpointer, quant_tree_meta
from repro.configs import get_config
from repro.models.model import build_model
from repro.serving.engine import Request, ServeConfig, ServeEngine
from repro.serving.weights import WeightStore, make_weight_pipeline


def _tiny(seed=0):
    cfg = get_config("granite-3-8b", reduced=True)
    cfg = dataclasses.replace(cfg, dtype="float32", n_layers=2, d_model=32,
                              n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
                              vocab=64)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed)), cfg


def _codes_cfg(bits):
    return ServeConfig(max_batch=2, max_len=32, quantize_weights="squant",
                       weight_bits=bits, dequantize_for_compute=False)


def _quantized(model, params, bits):
    """``(tree, quant_meta)``: what a real-code w``bits`` engine serves."""
    tree, report = make_weight_pipeline(model, _codes_cfg(bits))[1](params)
    return tree, quant_tree_meta(bits, "squant", 128, report=report)


def _reqs(n, max_new=4):
    return [Request(prompt=[1 + i % 5, 2, 3], max_new_tokens=max_new,
                    request_id=i) for i in range(n)]


def test_initial_version_and_properties():
    model, params, _ = _tiny()
    eng = ServeEngine(model, params,
                      ServeConfig(max_batch=2, max_len=32,
                                  quantize_weights="squant", weight_bits=8))
    assert eng.store.version == 1
    assert eng.quant_report is not None and eng.quant_report.layers
    assert eng.params is eng.store.current.params
    st = eng.store.stats()
    assert st["version"] == 1 and st["swaps"] == 0
    assert st["source"] == "init" and not st["errors"]
    out = eng.generate(_reqs(2))
    assert all(o.weights_version == 1 for o in out)
    assert all(o.swap_ms >= 0.0 for o in out)


def test_swap_never_lands_mid_round():
    """A version staged during decode becomes visible only at the next
    round boundary: round 1 serves v1 end-to-end (token-identical to an
    engine that never reloads), round 2 serves v2."""
    model, params, _ = _tiny(0)
    _, params2, _ = _tiny(1)
    scfg = ServeConfig(max_batch=2, max_len=32, quantize_weights="squant",
                       weight_bits=8)
    eng = ServeEngine(model, params, scfg)
    control = ServeEngine(model, params, scfg)

    fired = []
    orig_decode = eng._decode

    def hooked(p, cur, cache):
        if not fired:
            fired.append(True)
            # stage synchronously MID-ROUND: fully built before round ends
            eng.store.stage(fp_params=params2, source="midround",
                            block=True)
        return orig_decode(p, cur, cache)

    eng._decode = hooked
    outs = eng.generate(_reqs(4, max_new=4))        # 2 rounds of 2
    ctrl = control.generate(_reqs(4, max_new=4))
    assert fired, "decode hook never ran"
    r1, r2 = outs[:2], outs[2:]
    assert all(o.weights_version == 1 for o in r1)
    assert all(o.weights_version == 2 for o in r2)
    # round 1 never saw the staged tree: bit-identical to the no-reload run
    for a, b in zip(r1, ctrl[:2]):
        assert a.tokens == b.tokens
    log = eng.stats()["round_log"]
    assert [e["version"] for e in log] == [1, 2]
    assert eng.store.swap_count == 1
    assert all("swap_ms" in e and "prefill_ms" in e and "decode_ms" in e
               for e in log)


def test_background_stage_latest_wins():
    built = []

    def slow_quantize(tree):
        time.sleep(0.05)
        built.append(tree["tag"])
        return tree, None

    store = WeightStore(slow_quantize, fp_params={"tag": 0,
                                                  "w": jnp.zeros(2)})
    for i in (1, 2, 3):
        store.stage(fp_params={"tag": i, "w": jnp.zeros(2)},
                    source=f"s{i}")
    assert store.wait_staged(timeout=10)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        live, _ = store.acquire()
        if live.params["tag"] == 3:
            break
        time.sleep(0.01)
    assert live.params["tag"] == 3          # newest request won
    assert store.version == live.version
    assert not store.errors
    store.close()


def test_watcher_quantizes_fp_checkpoints_on_the_fly(tmp_path):
    model, params, _ = _tiny(0)
    _, params2, _ = _tiny(1)
    eng = ServeEngine(model, params,
                      ServeConfig(max_batch=2, max_len=32,
                                  quantize_weights="squant", weight_bits=8))
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save(1, params2, {"m": jnp.zeros(1)})     # training-style fp save
    expect = {"quantize_weights": "squant", "weight_bits": 8}
    assert eng.store.poll_checkpoints(ck, expect=expect) == 1
    out = eng.generate(_reqs(2))
    assert all(o.weights_version == 2 for o in out)
    cur = eng.store.current
    assert cur.source == "ckpt:1" and cur.step == 1
    assert cur.report is not None            # re-quantized via quantize_tree
    # same step polls as a no-op
    assert eng.store.poll_checkpoints(ck, expect=expect) is None


def test_watcher_loads_quantized_checkpoints_natively(tmp_path):
    model, params, _ = _tiny(0)
    _, params2, _ = _tiny(1)
    qtree, meta = _quantized(model, params2, 8)
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save_serving(5, qtree, quant_meta=meta)
    eng = ServeEngine(model, params,
                      ServeConfig(max_batch=2, max_len=32,
                                  quantize_weights="squant", weight_bits=8))
    assert eng.store.poll_checkpoints(
        ck, expect={"quantize_weights": "squant", "weight_bits": 8}) == 5
    out = eng.generate(_reqs(2))
    assert all(o.weights_version == 2 for o in out)
    assert all(len(o.tokens) == 4 for o in out)


def _break_step(dirname, mode):
    if mode == "torn":
        os.remove(os.path.join(dirname, "COMMITTED"))
    else:
        with open(os.path.join(dirname, "index.json"), "w") as f:
            f.write('{"step": 3, "trees": {')       # truncated json


@pytest.mark.parametrize("kind", ["fp", "quantized"])
def test_watcher_skips_torn_and_corrupt_steps(tmp_path, kind):
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
    _break_step(str(tmp_path / "step_00000002"), "torn")
    _break_step(str(tmp_path / "step_00000003"), "corrupt")
    assert ck.list_steps() == [1]
    eng = ServeEngine(model, params,
                      ServeConfig(max_batch=2, max_len=32,
                                  quantize_weights="squant", weight_bits=8))
    expect = {"quantize_weights": "squant", "weight_bits": 8}
    assert eng.store.poll_checkpoints(ck, expect=expect) == 1
    assert not eng.store.errors
    # a later valid step is picked up past the broken ones
    save(4, params2)
    assert eng.store.poll_checkpoints(ck, expect=expect) == 4
    out = eng.generate(_reqs(2))
    assert all(o.weights_version == 3 for o in out)     # init + 2 reloads


def test_watcher_rejects_meta_mismatch(tmp_path):
    model, params, _ = _tiny(0)
    qtree, meta = _quantized(model, params, 4)
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save_serving(1, qtree, quant_meta=meta)
    eng = ServeEngine(model, params,
                      ServeConfig(max_batch=2, max_len=32,
                                  quantize_weights="squant", weight_bits=8))
    expect = {"quantize_weights": "squant", "weight_bits": 8}
    assert eng.store.poll_checkpoints(ck, expect=expect) is None
    assert eng.store.version == 1                      # nothing swapped in
    errs = eng.store.stats()["errors"]
    assert errs and "mismatch" in errs[0]
    # the bad step is remembered, not retried forever
    assert eng.store.poll_checkpoints(ck, expect=expect) is None
    assert len(eng.store.errors) == 1


def test_watcher_retries_transient_failures(tmp_path):
    """A restore that fails transiently (I/O hiccup) is retried on later
    polls — only metadata mismatches are permanent."""
    model, params, _ = _tiny(0)
    _, params2, _ = _tiny(1)
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save(1, params2, {"m": jnp.zeros(1)})
    eng = ServeEngine(model, params,
                      ServeConfig(max_batch=2, max_len=32,
                                  quantize_weights="squant", weight_bits=8))
    orig, flaked = ck.restore_serving, []

    def flaky(*a, **kw):
        if not flaked:
            flaked.append(True)
            raise OSError("disk hiccup")
        return orig(*a, **kw)

    ck.restore_serving = flaky
    expect = {"quantize_weights": "squant", "weight_bits": 8}
    assert eng.store.poll_checkpoints(ck, expect=expect) is None
    assert "retries left" in eng.store.errors[-1]
    assert eng.store.poll_checkpoints(ck, expect=expect) == 1   # retried
    assert eng.store.wait_staged(version=1, timeout=30)
    # success clears the retry budget: same step is not re-staged
    assert eng.store.poll_checkpoints(ck, expect=expect) is None


def test_live_reload_zero_failed_requests(tmp_path):
    """Acceptance: a live reload during multi-round generation completes
    with zero failed/corrupted requests and the swapped-in version is
    observable in engine stats."""
    model, params, _ = _tiny(0)
    _, params2, _ = _tiny(1)
    eng = ServeEngine(model, params,
                      ServeConfig(max_batch=2, max_len=32,
                                  quantize_weights="squant", weight_bits=8))
    eng.watch_checkpoints(str(tmp_path), poll_s=0.02)
    ck = Checkpointer(str(tmp_path), async_save=False)

    def writer():
        time.sleep(0.05)
        ck.save(1, params2, {"m": jnp.zeros(1)})

    th = threading.Thread(target=writer)
    th.start()
    outs = []
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        outs.extend(eng.generate(_reqs(4, max_new=3)))     # 2 rounds/call
        if outs[-1].weights_version >= 2:
            break
    th.join()
    assert outs[-1].weights_version >= 2, "reload never landed"
    # zero failed/corrupted requests: every completion fully decoded
    assert all(len(o.tokens) == 3 for o in outs)
    versions = [e["version"] for e in eng.stats()["round_log"]]
    assert versions == sorted(versions)                     # monotonic
    st = eng.stats()["weights"]
    assert st["swaps"] >= 1 and st["version"] >= 2
    assert st["source"] == "ckpt:1"
    assert not st["errors"]
    eng.close()
    assert not eng.store.stats()["watching"]


def test_engine_from_prebuilt_qdict_store():
    """An externally staged serving tree (native quantized format) drives
    the engine without any fp params or quantize call."""
    model, params, _ = _tiny(0)
    qtree, _ = _quantized(model, params, 8)
    store = WeightStore(serving_params=qtree, source="prequantized")
    eng = ServeEngine(model, cfg=_codes_cfg(8), store=store)
    out = eng.generate(_reqs(2))
    assert all(len(o.tokens) == 4 for o in out)
    assert eng.store.current.source == "prequantized"
