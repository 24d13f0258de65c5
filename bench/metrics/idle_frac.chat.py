"""Device, chat decode: share of the traced window with no operation
running."""
from bench.roofline import idle_share

UNIT = "%"


def read(ctx):
    return idle_share(ctx)
