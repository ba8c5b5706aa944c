"""Device idle while a payload call is open, percent of the traced window:
the payloads' host work (per-token Python loop, lock, waits, batches)."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx, dispatch=False)
