"""Percent of the DeepDriveMD payload calls' host time spent waiting for
the train state's lock (the program's ``ddmd:lock`` spans over its
``ddmd:<kind>`` spans)."""
from bench import spans


def read(ctx):
    return spans.lock_wait_share(ctx.trace.spans)
