"""Device idle with no payload call open, percent of the traced window
(DeepDriveMD cells): executor and engine time between payloads."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx, dispatch=True)
