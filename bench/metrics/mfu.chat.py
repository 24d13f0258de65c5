"""Model step, chat decode: useful FLOPs (real rows at their true context,
trunk plus one head for each row that emitted a token) over the device
busy time at the chip's peak."""
from bench.roofline import step_mfu

UNIT = "%"


def read(ctx):
    return step_mfu(ctx)
