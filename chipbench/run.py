"""Run one benchmark cell once on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic come from ``BENCHMARK.json``
and the data files under ``chipbench/``. The run refuses any platform but
a TPU, and fewer chips than the cell asks for: it then exits non-zero and
prints no result. Otherwise the last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` with ``--trace 1``, and ``checks`` last); the numbers
compared for ``correct`` are also the last lines of standard error.

``--control 1`` (not used by the benchmark's own runs) also reads the
lower-precision control on the same outputs and prints its numbers under
``control``: the readings the limits in ``cells/*.json`` were set from.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"       # fixed: the path is part of the key
TRACE_DIR = ROOT / ".chipbench_trace"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    import harness
    try:
        man = harness.load_manifest(ROOT)
    except FileNotFoundError:
        print("no BENCHMARK.json next to chipbench/", file=sys.stderr)
        return 2
    cells = {w["name"]: w for w in man["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    w = cells[args.workload]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"the program is not next to the benchmark ({src})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    # the compile cache lives inside the checkout at a fixed path, whatever
    # the environment brought
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}; this benchmark "
              f"runs only on the chip", file=sys.stderr)
        return 3
    if len(devices) < w["chips"]:
        print(f"cell {w['name']} needs {w['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    setup = harness.SetupMeter()
    jax.monitoring.register_event_duration_secs_listener(setup.on_duration)
    jax.monitoring.register_event_listener(setup.on_event)

    ctx = harness.Context(
        root=ROOT, cell_name=w["name"],
        cell=harness.load_json(HERE / "cells" / f"{w['name']}.json"),
        config=harness.load_json(ROOT / conf["file"]),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, control=bool(args.control), trace_dir=TRACE_DIR)
    driver = harness.load_module("drivers", ctx.cell["driver"])
    rec = driver.run(ctx)
    harness.log(f"compiles {setup.compile_s:.1f} s, persistent cache "
                f"{setup.hits} hits / {setup.misses} misses; compile events "
                f"inside the window: "
                f"{setup.inside(*rec.layer['window_t'])}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    line = harness.result_line(man, w["name"], rec, ctx.trace, device)
    if ctx.control:
        line["control"] = rec.extra.get("control", {})
        line["checks"] = line.pop("checks")
    if rec.trace is not None:
        harness.log("trace " + json.dumps(
            dict(rec.trace, device_ops=rec.trace["device_ops"][:40],
                 idle_gaps=rec.trace["idle_gaps"][:40])))
    harness.log("extra " + json.dumps(rec.extra))
    for c in rec.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
