"""Engine (serving/executor.py), scoring: share of the traced window in
which the device was idle while the serving thread was in ``engine.pad``
(stacking the payloads, moving them to the device) or ``engine.dispatch``
(parameter lookups, enqueueing the prefix and the heads); bench/spans.py."""
from bench.spans import idle_share

UNIT = "%"


def read(ctx):
    return idle_share(ctx, "prep")
