"""Device time of a program under one ``jax.named_scope``, for the
per-layer metric readers in ``bench/metrics`` (the program opens the
scope; ``bench/trace.py`` keeps each program's self time by scope path)."""

from __future__ import annotations


def scope_ms(ctx, program: str, scope: str) -> float | None:
    """Device self time of ``program``'s operations under the named scope
    ``scope`` (a component of their scope path), ms per run; None where
    the program did not run or opens no such scope."""
    runs = ctx.trace.programs.get(program)
    paths = ctx.trace.scopes.get(program, {})
    ns = sum(v[1] for k, v in paths.items() if scope in k.split("/"))
    if not runs or not runs[0] or not ns:
        return None
    return ns / runs[0] / 1e6
