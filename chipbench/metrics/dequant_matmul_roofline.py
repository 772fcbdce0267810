"""``dequant_matmul``'s share of its roofline over the traced window (see
``_roofline.share`` and ``kernels/dequant_matmul.py``)."""
import harness


def read(rec):
    return harness.load_module("metrics", "_roofline").share(rec, "dequant_matmul")
