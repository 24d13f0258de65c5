"""Kernel decode_attention (kernels/decode_attention.py), chat decode: share of its
roofline, the work its calls in the window needed (bench/costs.py: real
rows at their true lengths) against its device time in the trace."""
from bench.roofline import kernel_share

UNIT = "%"
PATTERN = r"decode_attention"


def read(ctx):
    return kernel_share(ctx, "decode_attention", PATTERN)
