"""The reduction of the program's own names and spans (``programs.py``),
the readers built on it, and ``program_trace.py``'s additions to a run."""
from pathlib import Path

import pytest

import harness
import program_trace
import programs as P
from conftest import tiny_context

DATA = Path(__file__).resolve().parent / "data" / "probe.xplane.pb"


def _span(name, a, b, thread="python", **stats):
    return P.Span(name, float(a), float(b), thread, stats)


def _rec(kind="serve", trace=None, rows=()):
    return harness.Record(e2e={}, checks=[], attempted=1, failed=0,
                          memory_peak_bytes=0, trace=trace,
                          layer={"kind": kind, "rows": list(rows)})


def test_programs_clip_count_and_time_whole_launches():
    mods = [[("jit_serve_decode(11)", 0.0, 30.0),      # starts before w0
             ("jit_serve_decode(11)", 40.0, 20.0),
             ("jit_serve_decode(11)", 70.0, 10.0),
             ("jit_serve_scatter(7)", 85.0, 30.0),      # runs past w1
             ("jit_serve_copy(3)", 120.0, 5.0)]]        # after the window
    got = P.programs(mods, 10.0, 100.0)
    assert set(got) == {"jit_serve_decode", "jit_serve_scatter"}
    dec = got["jit_serve_decode"]
    assert dec["launches"] == 3
    assert dec["device_s"] == pytest.approx((20 + 20 + 10) * 1e-9)
    assert dec["durations_s"] == pytest.approx([20e-9, 10e-9])
    sc = got["jit_serve_scatter"]
    assert (sc["launches"], sc["durations_s"]) == (1, [])
    assert sc["device_s"] == pytest.approx(15e-9)


def test_nesting_self_time_and_idle_by_innermost_span():
    spans = P.nest([
        _span("serve.step", 0, 60, step=1),
        _span("serve.pull", 20, 40),
        _span("serve.on_step", 45, 55),
        _span("kv.decode", 56, 59),
        _span("serve.step", 62, 68, step=2),
        _span("quantize.tree", 0, 200, thread="other")])  # another thread
    by = {(s.name, s.start): s for s in spans}
    step = spans.index(by[("serve.step", 0.0)])
    assert by[("serve.pull", 20.0)].parent == step
    assert by[("kv.decode", 56.0)].parent == step
    assert by[("serve.step", 62.0)].parent is None
    assert by[("quantize.tree", 0.0)].parent is None
    table = P.span_table(spans, 0.0, 100.0)
    assert table["serve.step"]["count"] == 2
    assert table["serve.step"]["total_s"] == pytest.approx(66e-9)
    # 60 less pull 20, on_step 10 and decode 3; plus the second step's 6
    assert table["serve.step"]["self_s"] == pytest.approx(33e-9)
    assert table["serve.pull"]["self_s"] == pytest.approx(20e-9)
    # gaps: [5,10) under step, [25,35) under pull, [46,54) under on_step,
    # [70,90) under no span of the serving thread: the other thread's
    # longer span is all that covers it
    ops = [[("%f = fusion()", 0.0, 5.0), ("%f = fusion()", 10.0, 15.0),
            ("%f = fusion()", 35.0, 11.0), ("%f = fusion()", 54.0, 16.0),
            ("%f = fusion()", 90.0, 10.0)]]
    gaps = P.idle_gaps(ops, 0.0, 100.0)
    assert gaps == [(5.0, 10.0), (25.0, 35.0), (46.0, 54.0), (70.0, 90.0)]
    idle = dict(P.idle_by_span(gaps, spans))
    assert idle == pytest.approx({"serve.step": 5e-9, "serve.pull": 10e-9,
                                  "serve.on_step": 8e-9,
                                  "quantize.tree": 20e-9})
    alone = [s for s in spans if s.thread == "python"]
    idle = dict(P.idle_by_span(gaps, P.nest(alone)))
    assert idle[P.OUTSIDE] == pytest.approx(20e-9)


def test_reduce_reads_programs_of_recorded_trace():
    r = P.reduce(*P.read(DATA))
    progs = r["programs"]
    assert {k: v["launches"] for k, v in progs.items()} == {
        "jit_squant_pallas": 2, "jit_paged_decode_pallas": 2,
        "jit_dequant_matmul_pallas": 2}
    assert progs["jit_squant_pallas"]["durations_s"] == pytest.approx(
        [393.4225e-6, 393.23125e-6], rel=1e-5)
    # the probe predates the program spans: all idle lies outside them
    assert r["spans"] == {}
    assert [k for k, _ in r["idle_by_span"]] == [P.OUTSIDE]


def test_readers_on_synthetic_records():
    def load(name):
        return harness.load_module("metrics", name).read

    tr = {"window_s": 2.0, "busy_s": 1.5,
          "programs": {
              "jit_serve_decode": {"device_s": 1.2, "launches": 3,
                                   "durations_s": [0.3, 0.1, 0.2]},
              "jit_serve_prefill_chunk": {"device_s": 0.1, "launches": 1,
                                          "durations_s": [0.1]},
              "jit_serve_scatter": {"device_s": 0.1, "launches": 1,
                                    "durations_s": [0.1]}},
          "spans": {"serve.step": {}, "quantize.tree": {}},
          "idle_by_span": [["serve.pull", 0.2], ["serve.on_step", 0.1],
                           ["kv.decode", 0.05], ["quantize.sync", 0.4],
                           ["quantize.stack", 0.02], [P.OUTSIDE, 0.3]]}
    serve, quant = _rec("serve", tr), _rec("quantize", tr)
    assert load("decode_program_ms_p50")(serve) == pytest.approx(200.0)
    assert load("chunk_program_share")(serve) == pytest.approx(10.0)
    assert load("host_idle_share.serve")(serve) == pytest.approx(12.5)
    assert load("host_idle_share.quantize")(quant) == pytest.approx(1.0)
    rows = [(0,) * 8 + (30, 120), (0,) * 8 + (0, 0), (0,) * 8 + (10, 40)]
    assert load("kv_walk_useful_share")(_rec(rows=rows)) == \
        pytest.approx(25.0)
    # nothing to read: no trace, the other kind, a program without names,
    # spans or counters
    bare = dict(tr, programs={"jit_counted": {"device_s": 1.0}}, spans={})
    for name, rec in [("decode_program_ms_p50", _rec()),
                      ("decode_program_ms_p50", quant),
                      ("decode_program_ms_p50", _rec("serve", bare)),
                      ("chunk_program_share", _rec("serve", bare)),
                      ("host_idle_share.serve", _rec("serve", bare)),
                      ("host_idle_share.serve", quant),
                      ("host_idle_share.quantize", _rec("quantize", bare)),
                      ("kv_walk_useful_share", _rec(rows=[(0,) * 8])),
                      ("kv_walk_useful_share",
                       _rec(rows=[(0,) * 8 + (None, None)]))]:
        assert load(name)(rec) is None, name


@pytest.mark.parametrize("cell", ["granite-3-8b-pp2.chat",
                                  "rwkv6-1.6b.squant-w4"])
def test_program_trace_adds_to_a_traced_cpu_run(cell, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "load_module", program_trace.load_module)
    ctx = tiny_context(cell, 3000000019, seconds=1.0)
    ctx.trace, ctx.trace_dir = True, tmp_path / "trace"
    rec = harness.load_module("drivers", ctx.cell["driver"]).run(ctx)
    names = rec.trace["spans"]
    line = program_trace.result_line(harness.load_manifest(), cell, rec,
                                     True, {"platform": "cpu"})
    assert list(line)[-1] == "checks"
    assert line["e2e_traced"] == rec.e2e
    if cell.endswith("chat"):
        assert {"serve.step", "serve.chunk", "kv.decode"} <= set(names)
        assert all(len(r) == 10 for r in rec.layer["rows"])
        assert 0 < line["metrics"]["kv_walk_useful_share"]["value"] < 100
        assert "host_idle_share.serve" in line["metrics"]
    else:
        assert {"quantize.tree", "quantize.chunk"} <= set(names)
        assert "host_idle_share.quantize" in line["metrics"]
