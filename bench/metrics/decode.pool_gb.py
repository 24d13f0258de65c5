"""Decoder (serving/decode.py), chat: bytes of the paged KV pool on the
device (its K and V arrays), in GB."""
UNIT = "GB"


def read(ctx):
    nbytes = getattr(ctx, "pool_bytes", None)
    return None if nbytes is None else nbytes / 1e9
