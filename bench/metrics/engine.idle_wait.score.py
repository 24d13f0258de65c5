"""Engine (serving/executor.py), scoring: share of the traced window in
which the device was idle while the serving thread waited in
``engine.wait`` (``block_until_ready``): the runtime's launch and
completion latency; bench/spans.py."""
from bench.spans import idle_share

UNIT = "%"


def read(ctx):
    return idle_share(ctx, "wait")
