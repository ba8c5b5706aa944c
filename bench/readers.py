"""Shared arithmetic of the per-layer metric readers in ``bench/metrics``.

Each reader takes a :class:`Context` and returns a number, or ``None``
where its trace holds nothing for it to read."""

from __future__ import annotations

import dataclasses

from bench import arch, flops
from bench.arch import DECODE, PREFILL, TRAIN  # noqa: F401 (the readers)
from bench.trace import PAYLOAD, Reduced


@dataclasses.dataclass
class Context:
    """What a reader reads: the reduced trace of the traced instance
    (device time by program, kernel and named scope; the program's host
    spans), the cell's configuration and traffic files, the chip's peaks,
    and the traced instance's counters: ``perf``, the executor's
    ``PerfCounters``, and ``payload``, the payloads' counters, as dicts."""
    trace: Reduced
    cfg: dict
    traffic: dict
    peaks: dict
    counters: dict


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


def step_mfu(ctx: Context) -> float | None:
    """Percent of bf16 peak over the steps' own device time."""
    work = secs = 0.0
    model = arch.of(ctx.cfg).step_flops(ctx.cfg, ctx.traffic["shapes"])
    for prog, f in model.items():
        runs = ctx.trace.programs.get(prog)
        if runs:
            work += runs[0] * f
            secs += runs[1] / 1e9
    if not secs:
        return None
    return 100.0 * work / (secs * ctx.peaks["bf16_flops"])


def roofline(ctx: Context, kernel: str) -> float | None:
    """Percent: the roofline's least time for every call of ``kernel`` in
    the window over the calls' summed device time.  None where the kernel
    did not run, or ran inside a program for which the architecture
    module gives no cost."""
    calls = ctx.trace.kernels.get(kernel)
    if not calls:
        return None
    costs = arch.of(ctx.cfg).kernel_costs(
        ctx.cfg, ctx.traffic["shapes"]).get(kernel, {})
    if set(calls) - set(costs):
        return None
    least = spent = 0.0
    for prog, (n, ns) in calls.items():
        t, _ = flops.least_seconds(*costs[prog], ctx.peaks)
        least += n * t
        spent += ns / 1e9
    return 100.0 * least / spent
