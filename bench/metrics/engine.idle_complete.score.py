"""Engine (serving/executor.py), scoring: share of the traced window in
which the device was idle while the serving thread was in
``engine.complete`` (slicing each row's result, recording its completion);
bench/spans.py."""
from bench.spans import idle_share

UNIT = "%"


def read(ctx):
    return idle_share(ctx, "complete")
