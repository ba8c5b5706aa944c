"""Mean host time from a task's submit to its worker's first line, ms
(the ``handoff_us`` of the program's ``exec:task`` spans)."""
from bench import spans


def read(ctx):
    return spans.task_handoff_ms(ctx.trace.spans)
