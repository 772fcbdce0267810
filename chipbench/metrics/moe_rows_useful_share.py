"""Share of the rows the held experts' matmuls process that carry a token
routed to that expert: held token-expert pairs over held experts x
capacity, summed over the window's steps. Rows carry the step's counts
(the ``on_step`` keys ``moe_pairs_here`` and ``moe_rows_computed``) at
indices 10 and 11; rows without them come from a program that does not
count them."""


def read(rec):
    lay = rec.layer
    if lay.get("kind") != "serve":
        return None
    rows = [r for r in lay["rows"] if len(r) > 11 and r[11]]
    computed = sum(r[11] for r in rows)
    return 100.0 * sum(r[10] for r in rows) / computed if computed else None
