"""Shared arithmetic of the per-layer metric readers in ``bench/metrics``.

Each reader takes a :class:`Context` and returns a number, or ``None``
where its trace holds nothing for it to read."""

from __future__ import annotations

import dataclasses

from bench import flops
from bench.trace import PAYLOAD, Reduced

#: the program's jitted steps, by the name the trace gives their runs
TRAIN, PREFILL, DECODE = "jit_train_step", "jit_prefill", "jit_decode"


@dataclasses.dataclass
class Context:
    trace: Reduced
    cfg: dict
    traffic: dict
    peaks: dict


def idle_share(ctx: Context, dispatch: bool) -> float:
    """Percent of the window the device idled while no payload call was
    open (``dispatch``), or while one or more were."""
    idle = ctx.trace.idle_ns
    ns = sum(v for k, v in idle.items()
             if (k == "dispatch") == dispatch and
             (dispatch or k.startswith(PAYLOAD)))
    return 100.0 * ns / ctx.trace.window_ns


def step_ms(ctx: Context, program: str) -> float | None:
    runs = ctx.trace.programs.get(program)
    if not runs or not runs[0]:
        return None
    return runs[1] / runs[0] / 1e6


def mean_context(traffic: dict) -> float:
    """Mean cached positions a decode step attends to: a rollout's step t
    (from 0) sees t + 1."""
    return (traffic["shapes"]["decode_steps"] + 1) / 2


def step_flops(ctx: Context) -> dict:
    sh, cfg = ctx.traffic["shapes"], ctx.cfg
    return {
        TRAIN: flops.train_flops(cfg, sh["train_batch"], sh["train_seq"]),
        PREFILL: flops.prefill_flops(cfg, sh["prefill_batch"],
                                     sh["prefill_seq"]),
        DECODE: flops.decode_flops(cfg, sh["decode_batch"],
                                   mean_context(ctx.traffic)),
    }


def step_mfu(ctx: Context) -> float | None:
    """Percent of bf16 peak over the steps' own device time."""
    work = secs = 0.0
    for prog, f in step_flops(ctx).items():
        runs = ctx.trace.programs.get(prog)
        if runs:
            work += runs[0] * f
            secs += runs[1] / 1e9
    if not secs:
        return None
    return 100.0 * work / (secs * ctx.peaks["bf16_flops"])


def kernel_shapes(ctx: Context, kernel: str) -> dict:
    """program -> (FLOPs, bytes) of one call of ``kernel`` in it."""
    sh, cfg = ctx.traffic["shapes"], ctx.cfg
    if kernel == "flash_attention_pallas":
        return {TRAIN: flops.flash_cost(cfg, sh["train_batch"],
                                        sh["train_seq"]),
                PREFILL: flops.flash_cost(cfg, sh["prefill_batch"],
                                          sh["prefill_seq"])}
    if kernel == "decode_attention_pallas":
        return {DECODE: flops.decode_attn_cost(cfg, sh["decode_batch"],
                                               mean_context(ctx.traffic))}
    raise KeyError(kernel)


def roofline(ctx: Context, kernel: str) -> float | None:
    """Percent: the roofline's least time for every call of ``kernel`` in
    the window over the calls' summed device time.  None where the kernel
    did not run, or ran inside a program whose shapes are not known."""
    calls = ctx.trace.kernels.get(kernel)
    if not calls:
        return None
    shapes = kernel_shapes(ctx, kernel)
    if set(calls) - set(shapes):
        return None
    least = spent = 0.0
    for prog, (n, ns) in calls.items():
        t, _ = flops.least_seconds(*shapes[prog], ctx.peaks)
        least += n * t
        spent += ns / 1e9
    return 100.0 * least / spent
