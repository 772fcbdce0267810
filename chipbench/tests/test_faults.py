"""A run with the timed path broken underneath comes out not correct, and
the lower-precision control fails the number it is compared by. Each run
drives the whole cell (weights, engine or quantizer, window, reference) at
the program's reduced widths on the CPU; only the look for a chip is
skipped."""
import jax.numpy as jnp
import pytest

import harness

CHAT = "granite-3-8b-pp2.chat"
QUANT = "rwkv6-1.6b.squant-w4"
# limits for these reduced widths, between what sound runs and the control
# read here (the cells' own limits are set from chip readings)
TINY_GAP = 0.05
TINY_CHECK = {"limits": {"logit_gap_max": TINY_GAP}, "sample_requests": 3}


def test_sound_serving_run_is_correct_and_control_fails(run_tiny):
    import jax
    meter = harness.SetupMeter()
    jax.monitoring.register_event_duration_secs_listener(meter.on_duration)
    rec = run_tiny(CHAT, 11, control=True, check=TINY_CHECK)
    assert rec.correct and rec.attempted > 0
    # the warm-up compiled every program the window runs
    assert rec.layer["traced_in_window"] == {}
    assert meter.inside(*rec.layer["window_t"]) == {}
    gap = rec.checks[0].value
    ctl = rec.extra["control"]["logit_gap_max"]
    assert ctl > TINY_GAP > gap and ctl > 3 * gap


def _stale_decode(eng):
    """A decode step that hands back the cache it was given: K/V of the
    new token are never kept."""
    orig = eng._decode

    def step(params, tokens, cache):
        logits, _ = orig(params, tokens, cache)
        return logits, cache
    eng._decode = step


def test_decode_returning_its_state_unchanged_is_caught(run_tiny):
    rec = run_tiny(CHAT, 12, check=TINY_CHECK, faults=[_stale_decode])
    assert not rec.correct


def test_token_altered_where_sampled_is_caught(run_tiny, monkeypatch):
    from repro.serving import scheduler
    orig = scheduler.sample

    def altered(logits, key, temperature=0.0, top_k=0):
        t = orig(logits, key, temperature, top_k)
        return t.at[0].set((t[0] + 1) % logits.shape[-1])
    monkeypatch.setattr(scheduler, "sample", altered)
    rec = run_tiny(CHAT, 13, check=TINY_CHECK)
    assert not rec.correct


def test_sound_quantizer_run_is_correct(run_tiny):
    rec = run_tiny(QUANT, 14, check={"rows_per_leaf": 8, "limits": {
        "code_mismatch_max": 0.01, "scale_rel_err_max": 1e-6}})
    assert rec.correct


def test_code_altered_where_produced_is_caught(run_tiny):
    from repro.core.pipeline import quantize_tree
    from repro.quant.qtypes import QuantizedTensor

    def altered(params, **kw):
        tree, rep = quantize_tree(params, **kw)
        done = []

        def alter(x):
            if isinstance(x, QuantizedTensor) and not done:
                done.append(1)
                return QuantizedTensor(x.data ^ jnp.int8(1), x.scale, x.bits)
            return x
        import jax
        return jax.tree_util.tree_map(
            alter, tree, is_leaf=lambda x: isinstance(x, QuantizedTensor)
        ), rep
    rec = run_tiny(QUANT, 15, quantize_fn=altered,
                   check={"rows_per_leaf": 8, "limits": {
                       "code_mismatch_max": 0.01,
                       "scale_rel_err_max": 1e-6}})
    assert not rec.correct


@pytest.mark.parametrize("metric", ["batch_occupancy", "chunk_ms_share",
                                    "decode_step_ms_p50", "mfu.serve"])
def test_serving_readers_read_a_run(run_tiny, metric):
    rec = run_tiny(CHAT, 16, check=TINY_CHECK)
    v = harness.load_module("metrics", metric).read(rec)
    assert v is not None and v > 0
