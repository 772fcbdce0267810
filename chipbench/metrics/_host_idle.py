"""Shared arithmetic of the ``host_idle_share.*`` readers."""


def share(rec, kind, prefixes, excluded=()):
    """Idle seconds of the traced window whose gap lies under a program
    span named with one of ``prefixes`` (other than ``excluded``), over the
    window; None where the trace holds no such span."""
    tr = rec.trace
    if tr is None or rec.layer.get("kind") != kind or tr["window_s"] <= 0:
        return None
    if not any(n.startswith(prefixes) for n in tr.get("spans") or {}):
        return None
    idle = sum(s for n, s in tr["idle_by_span"]
               if n.startswith(prefixes) and n not in excluded)
    return 100.0 * idle / tr["window_s"]
