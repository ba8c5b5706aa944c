"""Device time of the train-step program per run, ms."""
from bench import readers


def read(ctx):
    return readers.step_ms(ctx, readers.TRAIN)
