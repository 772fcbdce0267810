"""Shared arithmetic of the ``<kernel>_roofline`` readers."""
import harness


def share(rec, kernel):
    """Least time of the window's logical work of ``kernel`` (the larger
    of operations over its peak and bytes over the HBM peak) over the
    kernel's device time in the trace; None where the trace has none."""
    tr, lay = rec.trace, rec.layer
    if tr is None or "work" not in lay or kernel not in lay["work"]:
        return None
    t = tr["kernels"].get(kernel)
    if not t:
        return None
    w, pk = lay["work"][kernel], lay["peaks"]
    least = max(w["flops"] / pk[harness.load_module("kernels", kernel).PEAK],
                w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
