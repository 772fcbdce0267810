"""Median device duration of the decode program (``jit_serve_decode``)
over its launches wholly inside the traced window: the device part of
``decode_step_ms_p50``'s host gap."""
import statistics


def read(rec):
    tr = rec.trace
    if tr is None or rec.layer.get("kind") != "serve":
        return None
    d = (tr.get("programs") or {}).get("jit_serve_decode", {}).get(
        "durations_s")
    return 1e3 * statistics.median(d) if d else None
