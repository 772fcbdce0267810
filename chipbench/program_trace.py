"""One traced run of a cell that also reads the program's own program
names, host spans and KV counters.

    python3 chipbench/program_trace.py --workload <cell> --seed <n> --seconds <s>

The run is ``run.py --trace 1``'s, with two additions: the tracer also
reduces the trace with ``programs.reduce`` before deleting it, and the
serving window keeps each step's ``positions_attended`` and
``positions_walked`` beside its row (indices 8 and 9). The last line of
standard output is ``run.py``'s, with ``metrics`` extended by the readers
in ``PROGRAM_METRICS`` and the traced run's end-to-end numbers under
``e2e_traced`` (set beside an untraced run of the same seed, they give
the cost of tracing). The per-program, per-span and idle-by-span tables
go to standard error.

``BENCHMARK.json`` does not list these metrics: ``run.py``'s tracer and
serving window keep none of what they read (PERF.md, open questions).
"""
from __future__ import annotations

import run  # first: its T_START opens setup_s

import argparse
import glob
import sys
from pathlib import Path

import harness
import programs
import trace

# reader name -> unit, as BENCHMARK.json would list them
PROGRAM_METRICS = {"decode_program_ms_p50": "ms", "chunk_program_share": "%",
                   "host_idle_share.serve": "%",
                   "host_idle_share.quantize": "%",
                   "kv_walk_useful_share": "%"}


class ProgramTracer(trace.Tracer):
    def reduce(self, kernels):
        paths = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        extra = programs.reduce(*programs.read(Path(paths[0]))) \
            if paths else {}
        out = super().reduce(kernels)
        out.update(extra)
        return out


def kv_window(window_cls):
    """The serving driver's window, keeping the step's decode counts."""
    class KVWindow(window_cls):
        def on_step(self, info):
            n = len(self.rows)
            try:
                super().on_step(info)
            finally:
                if len(self.rows) > n:
                    self.rows[-1] += (info.get("positions_attended"),
                                      info.get("positions_walked"))
    return KVWindow


_load_module = harness.load_module
_result_line = harness.result_line


def load_module(kind, name):
    mod = _load_module(kind, name)
    if kind == "drivers":
        mod.Tracer = ProgramTracer
        if hasattr(mod, "Window"):
            mod.Window = kv_window(mod.Window)
    return mod


def result_line(man, cell_name, rec, trace_, device):
    out = _result_line(man, cell_name, rec, trace_, device)
    for name, unit in PROGRAM_METRICS.items():
        v = harness.load_module("metrics", name).read(rec)
        if v is not None:
            out["metrics"][name] = {"value": v, "unit": unit}
    out["e2e_traced"] = rec.e2e
    out["checks"] = out.pop("checks")
    tr = rec.trace or {}
    progs = sorted((tr.get("programs") or {}).items(),
                   key=lambda kv: -kv[1]["device_s"])
    harness.log("programs (device s, launches): " + ", ".join(
        f"{k} {v['device_s']:.4f} {v['launches']}" for k, v in progs))
    spans = sorted((tr.get("spans") or {}).items(),
                   key=lambda kv: -kv[1]["self_s"])
    harness.log("spans (count, total s, self s): " + ", ".join(
        f"{k} {v['count']} {v['total_s']:.4f} {v['self_s']:.4f}"
        for k, v in spans))
    harness.log("idle by span (s): " + ", ".join(
        f"{k} {v:.4f}" for k, v in tr.get("idle_by_span") or []))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    harness.load_module = load_module
    harness.result_line = result_line
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
