"""``squant_flip``: SQuant's E, K and C stages on grouped rows
(``kernels/squant_flip.py`` in the program: ``squant_ek_kernel`` and
``squant_c_kernel``, two custom calls of ``squant_pallas``).

The least work of a quantization is to read each weight once and write
its code and its row's scale once; the rank comparisons are the kernel's
own method, not logical work, so the count is bytes alone.
"""
PATTERNS = (r"^%squant_pallas",)
PEAK = "bf16_flops"


def cost(m: int, n: int, bits: int, w_bytes: int = 2):
    """(operations, bytes) to quantize one ``(m, n)`` matrix."""
    return 0.0, m * n * w_bytes + m * n * bits / 8.0 + 4.0 * m
