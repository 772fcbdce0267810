"""The program's own names and host spans in a profiler trace, on the
device trace's clock.

The serving engine names its jitted programs ``serve_<name>``, which the
device's ``XLA Modules`` line shows as ``jit_serve_<name>(<hash>)``; the
scheduler, the paged KV cache and ``quantize_tree`` open host spans named
``serve.*``, ``kv.*`` and ``quantize.*`` (``jax.profiler.TraceAnnotation``),
which the profiler writes into the same ``.xplane.pb`` as the device ops.
:func:`reduce` turns one trace into three tables, all inside the window
(the host span ``trace.WINDOW_SPAN``):

* ``programs``: per program name (the module name without its hash) its
  device seconds clipped to the window and averaged over the devices, the
  launches that overlap the window, and the durations of the launches
  wholly inside it;
* ``spans``: per span name its count, total and self seconds, where self is
  the clipped duration less what its child spans cover (a span's parent is
  the span that encloses it on its thread);
* ``idle_by_span``: the window's idle seconds (the window less the union of
  the ``XLA Ops`` events, as ``trace.reduce`` computes it) summed by the
  innermost program span that covers each gap's middle, or
  ``outside program spans``.

A program that has no such names or spans gives empty tables.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

from trace import WINDOW_SPAN, union

PREFIXES = ("serve.", "kv.", "quantize.")
OUTSIDE = "outside program spans"
_HASH = re.compile(r"\(\d+\)$")

Event = Tuple[str, float, float]           # name, start_ns, duration_ns


@dataclasses.dataclass
class Span:
    name: str
    start: float                          # ns, on the trace's clock
    end: float
    thread: str                           # the host line it was recorded on
    stats: Dict[str, Any]
    parent: Optional[int] = None          # index of the enclosing span


def program_name(module: str) -> str:
    """``jit_serve_decode(1234)`` -> ``jit_serve_decode``."""
    return _HASH.sub("", module)


def read(path: Path):
    """``(ops, modules, spans, window)``: per device plane its ``XLA Ops``
    and its ``XLA Modules`` events, the program spans of the host plane,
    and the window span's ``(start_ns, end_ns)`` (None without one)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: List[List[Event]] = []
    modules: List[List[Event]] = []
    spans: List[Span] = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            for out, key in ((ops, "XLA Ops"), (modules, "XLA Modules")):
                ln = lines.get(key)
                out.append([(e.name, float(e.start_ns), float(e.duration_ns))
                            for e in ln.events] if ln is not None else [])
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for e in ln.events:
                    s, d = float(e.start_ns), float(e.duration_ns)
                    if e.name == WINDOW_SPAN and window is None:
                        window = (s, s + d)
                    elif e.name.startswith(PREFIXES):
                        spans.append(Span(e.name, s, s + d, ln.name,
                                          dict(e.stats)))
    return ops, modules, spans, window


def nest(spans: Sequence[Span]) -> List[Span]:
    """The spans ordered by thread and start, each with the index of the
    innermost span that encloses it on its thread."""
    out = sorted(spans, key=lambda s: (s.thread, s.start, -s.end))
    stack: List[int] = []
    for i, s in enumerate(out):
        while stack and (out[stack[-1]].thread != s.thread
                         or out[stack[-1]].end < s.end):
            stack.pop()
        s.parent = stack[-1] if stack else None
        stack.append(i)
    return out


def programs(modules: Sequence[Sequence[Event]], w0: float, w1: float
             ) -> Dict[str, Dict[str, Any]]:
    n_dev = max(len(modules), 1)
    out: Dict[str, Dict[str, Any]] = {}
    for ev in modules:
        for name, s, d in ev:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            p = out.setdefault(program_name(name), {
                "device_s": 0.0, "launches": 0, "durations_s": []})
            p["device_s"] += (b - a) / 1e9 / n_dev
            p["launches"] += 1
            if w0 <= s and s + d <= w1:
                p["durations_s"].append(d / 1e9)
    return out


def span_table(spans: Sequence[Span], w0: float, w1: float
               ) -> Dict[str, Dict[str, float]]:
    """Count, total and self seconds per span name (``spans`` nested)."""
    clip = [max(0.0, min(s.end, w1) - max(s.start, w0)) for s in spans]
    child = [0.0] * len(spans)
    for i, s in enumerate(spans):
        if s.parent is not None:
            child[s.parent] += clip[i]
    out: Dict[str, Dict[str, float]] = {}
    for i, s in enumerate(spans):
        if clip[i] <= 0:
            continue
        t = out.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                    "self_s": 0.0})
        t["count"] += 1
        t["total_s"] += clip[i] / 1e9
        t["self_s"] += (clip[i] - child[i]) / 1e9
    return out


def idle_gaps(ops: Sequence[Sequence[Event]], w0: float, w1: float
              ) -> List[Tuple[float, float]]:
    """The window less the busy union of each device's ops, over all
    devices."""
    gaps: List[Tuple[float, float]] = []
    for ev in ops:
        u = union([(max(s, w0), min(s + d, w1)) for _, s, d in ev
                   if min(s + d, w1) > max(s, w0)])
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    return gaps


def idle_by_span(gaps: Sequence[Tuple[float, float]],
                 spans: Sequence[Span], n_dev: int = 1
                 ) -> List[List[Any]]:
    """Idle seconds summed by the innermost span (``spans`` nested) that
    covers each gap's middle, largest first."""
    threads: Dict[str, List[int]] = defaultdict(list)
    for i, s in enumerate(spans):
        threads[s.thread].append(i)
    starts = {t: [spans[i].start for i in ix] for t, ix in threads.items()}
    total: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        mid = 0.5 * (a + b)
        best: Optional[Span] = None
        for t, ix in threads.items():
            k = bisect.bisect_right(starts[t], mid) - 1
            j = ix[k] if k >= 0 else None
            # spans on one thread nest, so the innermost one covering
            # ``mid`` encloses the last one to start before it
            while j is not None and spans[j].end < mid:
                j = spans[j].parent
            if j is not None and (best is None or spans[j].end
                                  - spans[j].start < best.end - best.start):
                best = spans[j]
        total[best.name if best else OUTSIDE] += (b - a) / 1e9 / n_dev
    return sorted(([k, v] for k, v in total.items()), key=lambda kv: -kv[1])


def reduce(ops, modules, spans, window) -> Dict[str, Any]:
    """The three tables of the module docstring; without a window span the
    window is the extent of the device ops."""
    if window is None:
        ends = [(s, s + d) for ev in ops for _, s, d in ev]
        window = (min(a for a, _ in ends), max(b for _, b in ends)) \
            if ends else (0.0, 0.0)
    w0, w1 = window
    nested = nest(spans)
    return {"programs": programs(modules, w0, w1),
            "spans": span_table(nested, w0, w1),
            "idle_by_span": idle_by_span(idle_gaps(ops, w0, w1), nested,
                                         max(len(ops), 1))}
