"""Flash attention kernel (prefill and training forward): roofline least
time over its device time, percent."""
from bench import readers


def read(ctx):
    return readers.roofline(ctx, "flash_attention_pallas")
