"""The executor's dispatcher passes and re-predictions, host ms per task
the passes started (the program's ``exec:pass`` and ``exec:predict``
spans)."""
from bench import spans


def read(ctx):
    return spans.engine_ms_per_task(ctx.trace.spans)
