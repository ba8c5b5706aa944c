"""Device time under the program's ``moe`` named scope (routing, the held
experts' grouped matmuls, the shared experts) per run of the decode
program, ms."""
from bench import readers, scope_time


def read(ctx):
    return scope_time.scope_ms(ctx, readers.DECODE, "moe")
