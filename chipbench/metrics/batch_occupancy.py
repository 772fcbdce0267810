"""Share of the slot pool that carried a token, averaged over the window's
steps (``recorded / max_slots`` of each ``on_step``)."""


def read(rec):
    lay = rec.layer
    if lay.get("kind") != "serve" or not lay["rows"]:
        return None
    rows = lay["rows"]
    return 100.0 * sum(r[1] for r in rows) / (len(rows) * lay["max_slots"])
