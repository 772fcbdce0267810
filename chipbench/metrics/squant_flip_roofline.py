"""``squant_flip``'s share of its roofline over the traced window (see
``_roofline.share`` and ``kernels/squant_flip.py``)."""
import harness


def read(rec):
    return harness.load_module("metrics", "_roofline").share(rec, "squant_flip")
