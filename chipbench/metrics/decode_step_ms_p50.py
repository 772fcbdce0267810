"""Median gap between consecutive steps that carried no prefill chunk and
no admission: the pure decode step, as the host sees it."""
import statistics


def read(rec):
    lay = rec.layer
    if lay.get("kind") != "serve":
        return None
    gaps = [r[7] * 1e3 for r in lay["rows"] if r[2] == 0 and r[3] == 0]
    return statistics.median(gaps) if gaps else None
