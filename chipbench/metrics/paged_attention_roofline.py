"""``paged_attention``'s share of its roofline over the traced window (see
``_roofline.share`` and ``kernels/paged_attention.py``)."""
import harness


def read(rec):
    return harness.load_module("metrics", "_roofline").share(rec, "paged_attention")
