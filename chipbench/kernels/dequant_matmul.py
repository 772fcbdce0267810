"""``dequant_matmul``: ``y = x @ (codes * scale).T`` for int8 or packed int4
codes (``kernels/dequant_matmul.py`` in the program).

Its trace events are the ``dequant_matmul_pallas`` custom calls. The work
is counted from the logical shapes: the rows that carry a token, not the
rows the kernel pads the batch to, so padding shows as lost share.
"""
PATTERNS = (r"^%dequant_matmul_pallas",)
PEAK = "bf16_flops"          # codes are widened to bf16 for the MXU


def cost(rows: int, m: int, n: int, bits: int, calls: int = 1,
         act_bytes: int = 2):
    """(operations, bytes) of ``calls`` calls over ``rows`` rows in all,
    each call reading the whole ``(m, n)`` weight once."""
    flops = 2.0 * rows * m * n
    weight = m * n * bits / 8.0 + 4.0 * m            # codes + f32 row scales
    act = rows * (n + m) * act_bytes                  # x in, y out
    return flops, calls * weight + act
