"""CPU tests of the benchmark: the harness, its data files, the trace
reduction, the references, the control and the planted faults, at sizes a
test run holds."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the shapes the CPU runs shrink a configuration to (widths of the
# program's reduced() presets; the cells' own widths need the chip)
TINY_ARCH = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "head_dim": 16, "d_ff": 128, "vocab": 256, "rwkv_head_dim": 16}
TINY = {
    "arch": TINY_ARCH,
    "serve": {"max_slots": 4, "max_len": 256, "kv_blocks": 0,
              "prefill_chunk": 32, "block_size": 16},
    "traffic": {"requests": 400,
                "prompt": {"median": 40, "sigma": 0.6, "min": 16,
                           "max": 128, "round": 16},
                "output": {"median": 16, "sigma": 0.5, "min": 4,
                           "max": 64}},
    "quantize": {"backend": "ref", "warm_calls": 1},
    "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def tiny_context(cell: str, seed: int, seconds: float = 1.5, **over):
    import time

    import harness
    man = harness.load_manifest()
    w = {x["name"]: x for x in man["workloads"]}[cell]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    ov = dict(TINY, **over)
    return harness.Context(
        root=harness.ROOT, cell_name=cell,
        cell=harness.load_json(harness.HERE / "cells" / f"{cell}.json"),
        config=harness.load_json(harness.ROOT / conf["file"]), seed=seed,
        seconds=seconds, trace=False, t_start=time.perf_counter(),
        control=over.pop("control", False) if "control" in over else False,
        overrides=ov)


@pytest.fixture
def run_tiny():
    import harness

    def go(cell, seed, control=False, **over):
        ctx = tiny_context(cell, seed, **over)
        ctx.control = control
        return harness.load_module("drivers", ctx.cell["driver"]).run(ctx)
    return go
