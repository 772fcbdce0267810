"""The whole quantization's share of the chip's peak: the least time of
the trees quantized in the window (every weight read once, its codes and
scales written once, at the HBM peak) over the window."""


def read(rec):
    lay = rec.layer
    if lay.get("kind") != "quantize" or "work" not in lay:
        return None
    return 100.0 * lay["trees"] * lay["work"]["tree_least_s"] / \
        lay["window_s"]
