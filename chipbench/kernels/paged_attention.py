"""``paged_attention``: one query token per slot against its K/V, read
through the block table (``kernels/paged_attention.py`` in the program).

Its trace events are the ``paged_decode_pallas`` custom calls. The work
is the context actually in use: for each live slot, the positions it
holds, not the ``max_len`` the block table spans.
"""
PATTERNS = (r"^%paged_decode_pallas",)
PEAK = "bf16_flops"


def cost(positions: int, rows: int, n_heads: int, n_kv: int, head_dim: int,
         kv_bytes: int = 2, q_bytes: int = 2):
    """(operations, bytes) for ``rows`` queries (slot-layers) over
    ``positions`` cached positions in all."""
    flops = 4.0 * positions * n_heads * head_dim      # q.k and p.v
    kv = 2.0 * positions * n_kv * head_dim * kv_bytes
    q = 2.0 * rows * n_heads * head_dim * q_bytes      # q in, out back
    return flops, kv + q
