"""Share of the traced window in which the chunked-admission programs ran
on the device: prefill chunks, monolithic prefills, the prefix gather, the
block scatter and the row admission (``jit_serve_*``). Unlike
``chunk_ms_share`` it does not count the host's wait for the decode step
still running when a chunk is dispatched."""

PROGRAMS = ("jit_serve_prefill_chunk", "jit_serve_prefill",
            "jit_serve_gather", "jit_serve_scatter", "jit_serve_admit")


def read(rec):
    tr = rec.trace
    if tr is None or rec.layer.get("kind") != "serve" or tr["window_s"] <= 0:
        return None
    progs = tr.get("programs") or {}
    if not any(k.startswith("jit_serve_") for k in progs):
        return None                      # a program without these names
    return 100.0 * sum(progs[k]["device_s"] for k in PROGRAMS
                       if k in progs) / tr["window_s"]
