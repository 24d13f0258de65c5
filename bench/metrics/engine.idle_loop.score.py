"""Engine (serving/executor.py), scoring: share of the traced window in
which the device was idle with the serving thread in the loop itself:
``engine.serve`` outside any micro-batch, an ``engine.poll`` sleep on empty
queues, or an ``engine.microbatch`` between its stages; bench/spans.py."""
from bench.spans import idle_share

UNIT = "%"


def read(ctx):
    return idle_share(ctx, "loop")
