"""The whole serving step's share of the chip's peak: the least time of all
the window's work (each decode step and each prefill chunk at the larger
of its operations over the bf16 peak and its bytes over the HBM peak)
over the window. It bounds what any layer's gain can give."""


def read(rec):
    lay = rec.layer
    if lay.get("kind") != "serve" or "work" not in lay:
        return None
    return 100.0 * lay["work"]["least_s"] / lay["window_s"]
