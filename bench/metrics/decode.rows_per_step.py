"""Decoder (serving/decode.py), chat: live rows a trunk step advances, the
mean over the window's steps, from the decoder's slots and completions
after each step."""
UNIT = "rows"


def read(ctx):
    rows = getattr(ctx, "rows_per_step", None)
    if not rows:
        return None
    return sum(rows) / len(rows)
