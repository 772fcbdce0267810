"""Profiler trace of the measured window, and its reduction to numbers.

``Tracer.start`` opens a profiler session (no Python tracer, so the host
pays little) and a host span ``chipbench.window``; ``stop`` closes both.
``reduce`` reads the ``.xplane.pb`` with nothing but JAX:

* device busy time: the union of the intervals of the ``XLA Ops`` events
  of each ``/device:TPU:<n>`` plane, clipped to the window span, averaged
  over the devices;
* per-kernel device time: the summed durations of the ops whose names
  match a kernel's patterns (``kernels/<kernel>.py``); a kernel with no
  matching op reads None;
* device ops by total time, named by their HLO instruction without its
  number;
* idle gaps (the window less the busy union), each labelled by the
  shortest host event that covers its middle (the harness's ``chipbench.*``
  spans, or the runtime's own), summed by label.
"""
from __future__ import annotations

import bisect
import glob
import re
import shutil
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "chipbench.window"
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s|=|$)")


class Tracer:
    def __init__(self, out_dir: Path):
        self.dir = Path(out_dir)
        self._span = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, kernels: Dict[str, Sequence[str]]) -> Dict:
        """The window's numbers (:func:`reduce`); the trace files are
        deleted afterwards, so a run writes little to disk."""
        paths = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        t0 = time.perf_counter()
        devices, host = read_planes(Path(paths[0]))
        out = reduce(devices, host, kernels)
        out["reduce_s"] = time.perf_counter() - t0
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def op_name(event_name: str) -> str:
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name[:48]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def read_planes(path: Path):
    """``(devices, host)``: per device plane the ``XLA Ops`` events, and
    all host events, as ``(name, start_ns, duration_ns)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: List[List[Tuple[str, float, float]]] = []
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ln = lines.get("XLA Ops") or lines.get("XLA Modules")
            devices.append(_events(ln) if ln is not None else [])
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend(_events(ln))
    return devices, host


def reduce(devices: Sequence[Sequence[Tuple[str, float, float]]],
           host: Sequence[Tuple[str, float, float]],
           kernels: Dict[str, Sequence[str]]) -> Dict:
    """Numbers of one traced window (see the module docstring)."""
    spans = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if spans:
        w0, w1 = spans[0]
    else:
        starts = [s for ev in devices for _, s, _ in ev]
        ends = [s + d for ev in devices for _, s, d in ev]
        w0, w1 = (min(starts), max(ends)) if starts else (0.0, 0.0)
    window_s = (w1 - w0) / 1e9
    pats = {k: [re.compile(p) for p in v] for k, v in kernels.items()}
    busy, ops = [], defaultdict(float)
    ktime: Dict[str, float] = defaultdict(float)
    khit: Dict[str, int] = defaultdict(int)
    gaps_all: List[Tuple[float, float]] = []
    for ev in devices:
        iv = []
        for name, s, d in ev:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            iv.append((a, b))
            ops[op_name(name)] += (b - a) / 1e9
            for k, ps in pats.items():
                if any(p.search(name) for p in ps):
                    ktime[k] += (b - a) / 1e9
                    khit[k] += 1
        u = union(iv)
        busy.append(sum(b - a for a, b in u) / 1e9)
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        gaps_all.extend((edges[i], edges[i + 1])
                        for i in range(0, len(edges), 2)
                        if edges[i + 1] > edges[i])
    n_dev = max(len(devices), 1)
    idle = _label_gaps(gaps_all, host)
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "kernels": {k: (ktime[k] / n_dev if khit[k] else None)
                    for k in kernels},
        "kernel_events": {k: khit[k] for k in kernels},
        "device_ops": sorted(([k, v / n_dev] for k, v in ops.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": [[k, v / n_dev] for k, v in idle],
    }


def _label_gaps(gaps, host) -> List[Tuple[str, float]]:
    """Idle seconds summed by the shortest host event covering each gap's
    middle (events longer than a second, and the window span itself, are
    too coarse to say what the host was doing)."""
    hs = sorted((s, s + d, n) for n, s, d in host
                if n != WINDOW_SPAN and 0 < d < 1e9)
    starts = [h[0] for h in hs]
    total: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        mid = 0.5 * (a + b)
        i = bisect.bisect_right(starts, mid)
        best: Optional[Tuple[float, str]] = None
        for j in range(i - 1, max(i - 257, -1), -1):
            s, e, n = hs[j]
            if e >= mid and (best is None or e - s < best[0]):
                best = (e - s, n)
        total[best[1] if best else "host: no span"] += (b - a) / 1e9
    return sorted(total.items(), key=lambda kv: -kv[1])

