"""Device time under the program's ``mla`` named scope (latent attention:
projections, the absorbed query, the latent cache's write and the decode
kernel) per run of the decode program, ms."""
from bench import readers, scope_time


def read(ctx):
    return scope_time.scope_ms(ctx, readers.DECODE, "mla")
