"""Host spans, program names and KV counters of the serving loop and of
``quantize_tree``, read back from a CPU profiler session.

The spans are ``jax.profiler.TraceAnnotation`` host events: the profiler
writes them into the same ``.xplane.pb`` as the device ops, so a trace of
the chip ties each idle gap to what the host was doing. Their metadata
arrives as event stats. The engine's jitted programs carry the names
``serve_<name>`` (``PjitFunction(serve_decode)`` on the host,
``jit_serve_decode`` on the device)."""
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.pipeline import quantize_tree
from repro.kernels.squant_flip import tile_rows
from repro.models.model import build_model
from repro.serving.engine import Request, ServeConfig, ServeEngine

PREFIXES = ("serve.", "kv.", "quantize.")


def _tiny(seed=0):
    cfg = get_config("granite-3-8b", reduced=True)
    cfg = dataclasses.replace(cfg, dtype="float32", n_layers=2, d_model=32,
                              n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
                              vocab=256)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def _paged(model, params, **over):
    base = dict(max_len=48, scheduler="continuous", max_slots=2,
                kv_backend="paged", block_size=4, prefill_chunk=4,
                swap_deadline_ms=None)
    base.update(over)
    return ServeEngine(model, params, ServeConfig(**base))


def _requests():
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(0, 256, n).tolist(),
                    max_new_tokens=m, request_id=10 + i)
            for i, (n, m) in enumerate([(9, 5), (14, 3), (6, 6), (11, 4)])]


def _profiled(tmp_path, fn):
    """``fn()`` under a profiler session; returns its result and the host
    events as ``(name, start_ns, end_ns, thread, stats)``."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                events.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                               ln.name, dict(e.stats)) for e in ln.events)
    return out, events


def _spans(events):
    """Program spans as ``(name, parent name, stats)``: a span's parent is
    the innermost span that encloses it on its thread."""
    spans = sorted((e for e in events if e[0].startswith(PREFIXES)),
                   key=lambda e: (e[3], e[1], -e[2]))
    out, stack = [], []
    for s in spans:
        while stack and (stack[-1][3] != s[3] or stack[-1][2] < s[2]):
            stack.pop()
        out.append((s[0], stack[-1][0] if stack else None, s[4]))
        stack.append(s)
    return out


def test_serving_spans_nest_and_name_programs(tmp_path):
    model, params = _tiny(0)
    _, params2 = _tiny(1)
    eng = _paged(model, params)
    eng.generate(_requests())                  # compile outside the trace
    staged = []

    def stage_once(info):
        if info["step"] >= 3 and not staged:
            staged.append(info["step"])
            eng.store.stage(fp_params=params2, source="midrun", block=True)
    eng.on_step = stage_once
    reqs = _requests()
    outs, events = _profiled(tmp_path, lambda: eng.generate(reqs))
    assert [len(o.tokens) for o in outs] == [r.max_new_tokens for r in reqs]
    spans = _spans(events)
    parent = {}
    for name, par, _ in spans:
        parent.setdefault(name, set()).add(par)
    assert parent == {
        "serve.step": {None}, "serve.swap": {"serve.step"},
        "serve.admit": {"serve.step"}, "serve.chunk": {"serve.step"},
        "serve.chunk_wait": {"serve.chunk"}, "kv.gather": {"serve.chunk"},
        "kv.scatter": {"serve.chunk"}, "serve.sample": {"serve.step"},
        "serve.pull": {"serve.step"}, "serve.retire": {"serve.step"},
        "serve.on_step": {"serve.step"}, "kv.decode": {"serve.step"}}
    ids = {r.request_id for r in reqs}
    chunks = [st for n, _, st in spans if n == "serve.chunk"]
    assert {st["request_id"] for st in chunks} == ids
    assert all(0 < st["tokens"] <= 4 for st in chunks)
    # one request's spans share its id: chunk, gather and scatter
    for n in ("kv.gather", "kv.scatter"):
        assert {st["request_id"] for m, _, st in spans if m == n} == ids
    admitted = [st for n, _, st in spans if n == "serve.admit"
                and st.get("requests")]
    assert {int(i) for st in admitted for i in str(st["request_ids"]).split()
            } == ids
    retired = " ".join(str(st["request_ids"]) for n, _, st in spans
                       if n == "serve.retire" and "request_ids" in st)
    assert sorted(int(i) for i in retired.split()) == sorted(ids)
    steps = [st["step"] for n, _, st in spans if n == "serve.step"]
    assert steps == sorted(steps)
    names = {e[0] for e in events}
    assert "PjitFunction(serve_decode)" in names
    assert "PjitFunction(serve_prefill_chunk)" in names
    assert not any("counted" in n for n in names)


@pytest.mark.parametrize("backend", ["auto", "interpret"])
def test_quantize_tree_spans(tmp_path, backend):
    _, params = _tiny(0)
    (_, rep), events = _profiled(tmp_path, lambda: quantize_tree(
        params, method="squant", bits=4, group_size=16, backend=backend))
    spans = _spans(events)
    parent = {}
    for name, par, _ in spans:
        parent.setdefault(name, set()).add(par)
    assert parent == {
        "quantize.tree": {None}, "quantize.plan": {"quantize.tree"},
        "quantize.chunk": {"quantize.tree"},
        "quantize.stack": {"quantize.chunk"},
        "quantize.dispatch": {"quantize.chunk"},
        "quantize.wrap": {"quantize.chunk"},
        "quantize.sync": {"quantize.tree"}}
    tree, = [st for n, _, st in spans if n == "quantize.tree"]
    assert tree == {"leaves": len(rep.layers), "buckets": len(rep.buckets)}
    chunks = [st for n, _, st in spans if n == "quantize.chunk"]
    assert [(c["bucket"], c["layers"]) for c in chunks] == \
        [(b.key, b.num_layers) for b in rep.buckets]
    # the kernel's tile height from each chunk's stacked rows; 0 off it
    for c, b in zip(chunks, rep.buckets):
        rows = b.num_layers * int(b.key[1:].split(",")[0])
        want = tile_rows(rows) if b.impl != "ref" else 0
        assert c["tile_rows"] == want
    assert any(b.impl != "ref" for b in rep.buckets) == (backend != "auto")


@pytest.mark.parametrize("chunk", [0, 4])
def test_decode_counts_positions_attended_and_walked(chunk):
    """Each step's info carries what the decode launch it sampled walked
    (the block tables handed to the program, times the block size) and
    attended (each active slot's context, new token included); the KV
    stats hold their running sums."""
    model, params = _tiny(0)
    eng = _paged(model, params, prefill_chunk=chunk)
    kv = eng.scheduler.kv
    launches = []
    decode, program = kv.decode, eng._decode

    def record_decode(params_, nxt, active_ids):
        launches.append({"attended": int(sum(kv._lengths[i] + 1
                                             for i in active_ids))})
        decode(params_, nxt, active_ids)

    def record_program(params_, tok, cache):
        launches[-1]["walked"] = (int(np.prod(cache["block_tables"].shape))
                                  * kv.block_size)
        return program(params_, tok, cache)

    kv.decode, eng._decode = record_decode, record_program
    infos = []
    eng.on_step = infos.append
    eng.generate(_requests())
    assert launches and all(l["walked"] == 2 * (48 // 4) * 4
                            for l in launches)
    got = [(i["positions_attended"], i["positions_walked"]) for i in infos]
    # a step samples the logits of the decode launched after the previous
    # step (every launch is sampled by a later step); the first step of a
    # wave samples admission logits and reports no launch
    assert [g for g in got if g != (0, 0)] == \
        [(l["attended"], l["walked"]) for l in launches]
    assert (0, 0) in got
    st = eng.scheduler.stats().kv
    assert st["positions_attended"] == sum(l["attended"] for l in launches)
    assert st["positions_walked"] == sum(l["walked"] for l in launches)
    assert 0 < st["positions_attended"] < st["positions_walked"]


def test_decode_walk_counts_the_kernels_live_groups(monkeypatch):
    """Where ``paged_attention`` resolves to the kernel (interpret mode
    here, the compiled kernel on a TPU), a launch walks each row's live
    page groups: ``ceil((len + 1) / (P * bs)) * P * bs`` positions for a
    row at position ``len``, retired and empty rows (at 0) one group."""
    import repro.kernels.paged_attention as pa
    auto = pa.resolve
    monkeypatch.setattr(pa, "resolve", lambda use: "interpret"
                        if use == "auto" else auto(use))
    model, params = _tiny(0)
    eng = _paged(model, params, max_len=512, prefill_chunk=0)
    kv = eng.scheduler.kv
    p = pa.pages_per_step(kv.block_size, 1, 16, 4, kv.nb_per_slot)
    group = p * kv.block_size
    assert p < kv.nb_per_slot
    positions, program = [], eng._decode

    def record_program(params_, tok, cache):
        positions.append(np.asarray(cache["pos"]))
        return program(params_, tok, cache)

    eng._decode = record_program
    eng.generate(_requests())
    assert positions and any((pos == 0).any() for pos in positions)
    want = [int(sum(-(-(int(n) + 1) // group) for n in pos)) * group
            for pos in positions]
    assert kv.stats()["positions_walked"] == sum(want)
    assert want[0] < len(positions[0]) * kv.nb_per_slot * kv.block_size


def test_contiguous_steps_carry_no_decode_counts():
    model, params = _tiny(0)
    eng = ServeEngine(model, params, ServeConfig(
        max_batch=2, max_len=32, scheduler="continuous"))
    infos = []
    eng.on_step = infos.append
    eng.generate(_requests()[:2])
    assert infos and not any("positions_walked" in i for i in infos)


def _moe_tiny(seed=0):
    """The reduced Moonlight stack holding 2 of its 4 routed experts (the
    experts 1 and 2), with a random selection bias."""
    cfg = get_config("moonshot-v1-16b-a3b", reduced=True)
    cfg = dataclasses.replace(
        cfg, dtype="float32", scan_layers=False,
        moe=dataclasses.replace(cfg.moe, n_held=2, first_held=1))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.asarray(rng.normal(0, 0.5, x.shape), x.dtype)
        if getattr(p[-1], "key", None) == "score_bias" else x, params)
    return model, params


def _held_pairs(model, params, seq):
    """Host recount: per position of ``seq``, the choices of the held
    experts in every MoE layer (the reference's hidden states and
    router)."""
    from repro.models import reference as ref
    cfg = model.cfg
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    out = np.zeros(len(seq), np.int64)
    lo, hi = cfg.moe.first_held, cfg.moe.first_held + cfg.moe.held
    with jax.default_matmul_precision("highest"):
        x = f32(params["embedding"]["embedding"])[jnp.asarray([seq])]
        for p, use_moe in ref._layers(params["stack"]):
            p = f32(p)
            if use_moe:
                h = x + ref.mla(ref.rms(x, p["ln1"]["gain"]), p["attn"], cfg)
                hn = ref.rms(h, p["ln2"]["gain"])
                _, idx = ref.route(hn @ p["moe"]["router"]["w"],
                                   p["moe"]["router"]["score_bias"],
                                   cfg.moe)
                idx = np.asarray(idx[0])
                out += ((idx >= lo) & (idx < hi)).sum(-1)
            x = ref.block(x, p, cfg, use_moe)
    return out


@pytest.mark.parametrize("chunk", [0, 4])
def test_moe_counters_match_a_host_recount(chunk):
    """Held pairs and held-expert rows per decode launch and prefill
    chunk: summed, they equal a host recount of the router's choices over
    every prompt position and every decoded token (the last token of a
    request is sampled, never fed back); ``stats()`` holds the running
    sums and each step's info only that step's share."""
    model, params = _moe_tiny()
    eng = _paged(model, params, prefill_chunk=chunk)
    kv = eng.scheduler.kv
    launches = []
    decode = kv.decode

    def count_decode(params_, nxt, active_ids):
        launches.append(len(active_ids))
        decode(params_, nxt, active_ids)
    kv.decode = count_decode
    infos = []
    eng.on_step = infos.append
    reqs = _requests()
    outs = eng.generate(reqs)
    want_pairs = sum(int(_held_pairs(model, params,
                                     list(r.prompt) + o.tokens[:-1]).sum())
                     for r, o in zip(reqs, outs))
    moe_layers = model.cfg.n_layers - model.cfg.first_k_dense
    want_rows = moe_layers * 2 * (len(launches) * 2
                                  + sum(len(r.prompt) for r in reqs))
    st = eng.scheduler.stats().kv
    assert st["moe_pairs_here"] == want_pairs > 0
    assert st["moe_rows_computed"] == want_rows
    # a held pair is a computed row, and most rows carry none
    assert st["moe_pairs_here"] < st["moe_rows_computed"]
    steps = [(i["moe_pairs_here"], i["moe_rows_computed"]) for i in infos]
    assert sum(p for p, _ in steps) == want_pairs
    assert sum(r for _, r in steps) == want_rows
    # per step, not running sums: the last step carries the last launch
    assert 0 < steps[-1][1] < want_rows
    eng.close()


def test_dense_stack_counts_no_expert_pairs():
    model, params = _tiny(0)
    eng = _paged(model, params)
    infos = []
    eng.on_step = infos.append
    eng.generate(_requests())
    assert infos and all(i["moe_pairs_here"] == i["moe_rows_computed"] == 0
                         for i in infos)
    st = eng.scheduler.stats().kv
    assert st["moe_pairs_here"] == st["moe_rows_computed"] == 0
    eng.close()
