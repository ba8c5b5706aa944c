"""Percent of the train-step program's device time under the ``moe``
named scope (routing, the held experts and the shared experts, forward,
recomputation and backward)."""
from bench import readers, scope_time


def read(ctx):
    ms = scope_time.scope_ms(ctx, readers.TRAIN, "moe")
    step = readers.step_ms(ctx, readers.TRAIN)
    if ms is None or not step:
        return None
    return 100.0 * ms / step
