"""Benchmark plumbing shared by ``run.py``, the drivers and the tests.

Everything that belongs to one cell, configuration, kernel or per-layer
metric lives in a file of its own and is found here by the name
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``  — the model configuration as it is run;
* ``cells/<cell>.json``      — driver, traffic parameters, serving or
  quantizer settings, and the limits of the output check;
* ``drivers/<driver>.py``    — one kind of measured window (``run(ctx)``);
* ``kernels/<kernel>.py``    — trace-event patterns and the operations and
  bytes of one kernel, from the logical shapes of the work;
* ``metrics/<metric>.py``    — one per-layer metric (``read(rec)``), which
  returns None where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def validate_manifest(man: Dict[str, Any]) -> List[str]:
    """The contract's static rules that a data-only PR can break; returns
    the problems found (empty when the manifest is sound)."""
    bad: List[str] = []
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(man) != keys:
        bad.append(f"top-level keys {sorted(man)}")
    names = set()

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME_RE.match(n):
            bad.append(f"{what} name {n!r}")

    configs = {c["name"]: c for c in man.get("configs", [])}
    for c in man.get("configs", []):
        name_ok(c["name"], "config")
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c['name']} keys {sorted(c)}")
        for k in c.get("reduced", []):
            name_ok(k, "reduced key")
    cells = {}
    for w in man.get("workloads", []):
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], "traffic")
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w['name']} keys {sorted(w)}")
        if w["config"] not in configs:
            bad.append(f"workload {w['name']} names no config")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']} chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            bad.append(f"workload {w['name']} why")
        cells[w["name"]] = w
    used = {w["config"] for w in cells.values()}
    for c in configs:
        if c not in used:
            bad.append(f"config {c} used by no cell")
    e2e = {}
    for m in man.get("end_to_end", []):
        name_ok(m["name"], "metric")
        if m["name"] in names:
            bad.append(f"metric {m['name']} twice")
        names.add(m["name"])
        if not UNIT_RE.match(m["unit"]) or m["better"] not in ("lower",
                                                                "higher"):
            bad.append(f"metric {m['name']} unit/better")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']} source {m['source']}")
        if not 0.01 <= m["bound"] <= 0.25:
            bad.append(f"metric {m['name']} bound {m['bound']}")
        if set(m) - {"name", "unit", "better", "bound", "source",
                     "workloads"}:
            bad.append(f"metric {m['name']} keys {sorted(m)}")
        e2e[m["name"]] = m
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for m in man.get("per_layer", []):
        name_ok(m["name"], "metric")
        if m["name"] in names:
            bad.append(f"metric {m['name']} twice")
        names.add(m["name"])
        if not UNIT_RE.match(m["unit"]) or m["better"] not in ("lower",
                                                                "higher"):
            bad.append(f"metric {m['name']} unit/better")
        if m["source"] not in SOURCES:
            bad.append(f"metric {m['name']} source {m['source']}")
        if set(m) - {"name", "unit", "better", "source", "layer", "moves",
                     "workloads"}:
            bad.append(f"metric {m['name']} keys {sorted(m)}")
        if m.get("moves") not in e2e:
            bad.append(f"metric {m['name']} moves {m.get('moves')!r}")
        if not m.get("workloads"):
            bad.append(f"metric {m['name']} lists no workloads")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"metric {m['name']} names cell {w}")
            elif m.get("moves") in e2e and w not in e2e_cells(
                    man, e2e[m["moves"]]):
                bad.append(f"metric {m['name']} in {w}, which does not "
                           f"report {m['moves']}")
    for w in cells:
        have = [m for m in e2e.values() if w in e2e_cells(man, m)]
        if len(have) < 2:
            bad.append(f"cell {w} reports {len(have)} end-to-end metrics")
        if not any(w in m.get("workloads", []) for m in
                   man.get("per_layer", [])):
            bad.append(f"cell {w} reports no per-layer metric")
    return bad


def e2e_cells(man: Dict[str, Any], metric: Dict[str, Any]) -> List[str]:
    """Cells that report ``metric`` (all cells when it lists none)."""
    return metric.get("workloads") or [w["name"] for w in man["workloads"]]


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under this directory, loaded by file path (names
    may hold dots)."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's data, the run's arguments, and (for
    CPU tests only) overrides that shrink the run and pick CPU kernels."""
    root: Path
    cell_name: str
    cell: Dict[str, Any]
    config: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t_start: float                       # perf_counter at process start
    control: bool = False                # also read the lower-precision control
    overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace_dir: Optional[Path] = None


@dataclasses.dataclass
class Check:
    """One number compared, with its limit (``value <= limit`` passes)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Record:
    """What a driver hands back; metric readers read it."""
    e2e: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    layer: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Optional[Dict[str, Any]] = None
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks) \
            and self.failed == 0 and self.attempted > 0


# configuration file keys (the source's names) -> the program's ArchConfig
_ARCH_KEYS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads",
              "intermediate_size": "d_ff", "vocab_size": "vocab",
              "tie_word_embeddings": "tie_embeddings",
              "rope_theta": "rope_theta", "torch_dtype": "dtype",
              "head_size": "rwkv_head_dim"}


def program_config(conf: Dict[str, Any], shrink: Optional[Dict] = None):
    """The program's ``ArchConfig`` for a configuration file: the registry
    entry ``conf["arch"]`` at ``num_hidden_layers`` layers, checked against
    every size the file states. ``shrink`` (CPU tests only) replaces
    fields after the check."""
    from repro.configs import get_config
    base = get_config(conf["arch"])
    cfg = dataclasses.replace(base, n_layers=int(conf["num_hidden_layers"]))
    for key, field in _ARCH_KEYS.items():
        if key in conf and getattr(cfg, field) != conf[key]:
            raise ValueError(f"{conf['name']}: {key}={conf[key]!r} but the "
                             f"program's {conf['arch']} has {field}="
                             f"{getattr(cfg, field)!r}")
    if shrink:
        cfg = dataclasses.replace(cfg, **shrink)
    return cfg


class SetupMeter:
    """Compile seconds and persistent-cache hits and misses, from JAX's
    monitoring events (the listeners ``chip_smoke.py`` registers)."""

    def __init__(self):
        self.compile_s = 0.0
        self.hits = self.misses = 0
        self.events: List[tuple] = []     # (perf_counter at end, name)

    def on_duration(self, event, duration, fun_name="", **_):
        if event.startswith("/jax/core/compile/"):
            self.compile_s += duration
            self.events.append((time.perf_counter(), fun_name or event))

    def inside(self, t0: float, t1: float) -> Dict[str, int]:
        """Compile events that ended between ``t0`` and ``t1``, by name:
        a window should hold none."""
        out: Dict[str, int] = {}
        for t, name in self.events:
            if t0 <= t <= t1:
                out[name] = out.get(name, 0) + 1
        return out

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def log(msg: str) -> None:
    """Progress on standard error (the result line is on standard out)."""
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def bytes_in_use(devices) -> int:
    return max(int((d.memory_stats() or {}).get("bytes_in_use", -1))
               for d in devices)


def peak_memory_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in devices)


def peaks_for(kind: str) -> Dict[str, float]:
    table = load_json(HERE / "peaks.json")["kinds"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in peaks.json")
    return table[kind]


def result_line(man, cell_name: str, rec: Record, trace: bool,
                device: Dict[str, Any]) -> Dict[str, Any]:
    """The last line of standard output: end-to-end metrics without a
    trace, per-layer metrics with one; ``checks`` comes last."""
    metrics: Dict[str, Dict[str, Any]] = {}
    if not trace:
        for m in man["end_to_end"]:
            if cell_name in e2e_cells(man, m) and m["name"] in rec.e2e:
                metrics[m["name"]] = {"value": rec.e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in man["per_layer"]:
            cells = m.get("workloads") or e2e_cells(
                man, {x["name"]: x for x in man["end_to_end"]}[m["moves"]])
            if cell_name not in cells:
                continue
            v = load_module("metrics", m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out: Dict[str, Any] = {"correct": rec.correct, "attempted": rec.attempted,
                           "failed": rec.failed, "metrics": metrics,
                           "device": dict(device,
                                          memory_peak_bytes=rec.
                                          memory_peak_bytes)}
    if trace and rec.trace is not None:
        out["device"]["busy_s"] = rec.trace["busy_s"]
        out["device"]["window_s"] = rec.trace["window_s"]
        out["breakdown"] = {"device_ops": rec.trace["device_ops"][:10],
                            "idle_gaps": rec.trace["idle_gaps"][:10]}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in rec.checks}
    return out
