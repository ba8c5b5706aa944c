"""Device time of the decode program per run, ms."""
from bench import readers


def read(ctx):
    return readers.step_ms(ctx, readers.DECODE)
