"""Kernel bank_matmul (kernels/bank_matmul.py), chat decode: share of its
roofline, the work its calls in the window needed (bench/costs.py: every
member's head over the step's bucket of rows) against its device time in
the trace."""
from bench.roofline import kernel_share

UNIT = "%"
PATTERN = r"bank_matmul"


def read(ctx):
    return kernel_share(ctx, "bank_matmul", PATTERN)
