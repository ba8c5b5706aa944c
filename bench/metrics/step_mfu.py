"""Model FLOPs of the train, prefill and decode runs in the window over
their summed device time times the bf16 peak, percent."""
from bench import readers


def read(ctx):
    return readers.step_mfu(ctx)
