"""Share of the window spent in chunked admission: the summed ``chunk_ms``
of the window's steps over the window."""


def read(rec):
    lay = rec.layer
    if lay.get("kind") != "serve" or not lay["rows"]:
        return None
    return 100.0 * sum(r[2] for r in lay["rows"]) / (1e3 * lay["window_s"])
