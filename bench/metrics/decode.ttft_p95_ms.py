"""Decoder (serving/decode.py), chat: nearest-rank 95th percentile of the
time to first token of every request sent in the window, from its submit
to the end of the step that emitted its first token, on the run's host
clock; a request still open at the close finishes in the drain and its
wait counts.  Prompts stream in a token a step, so this follows prompt
length times step time."""
import math

UNIT = "ms"


def read(ctx):
    ttft = sorted(getattr(ctx, "ttft_s", None) or ())
    if not ttft:
        return None
    return 1e3 * ttft[max(0, math.ceil(0.95 * len(ttft)) - 1)]
