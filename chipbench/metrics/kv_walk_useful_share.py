"""Share of the positions the paged decode program walks that a live slot
attends, summed over the window's steps. Rows carry the step's decode
counts (the ``on_step`` keys ``positions_attended`` and
``positions_walked``) at indices 8 and 9; rows without them come from a
program that does not count them."""


def read(rec):
    lay = rec.layer
    if lay.get("kind") != "serve":
        return None
    rows = [r for r in lay["rows"] if len(r) > 9 and r[9]]
    walked = sum(r[9] for r in rows)
    return 100.0 * sum(r[8] for r in rows) / walked if walked else None
