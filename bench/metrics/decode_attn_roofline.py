"""Decode attention kernel: roofline least time over its device time,
percent."""
from bench import readers


def read(ctx):
    return readers.roofline(ctx, "decode_attention_pallas")
