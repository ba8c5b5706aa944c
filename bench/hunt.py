"""Per-instance counters over a long window, for finding what holds a
stalled instance.

    python bench/hunt.py --workload <cell> --seed <n> --seconds <s> \\
        [--traced <k>] [--out <file.json>]

Set-up as ``bench/run.py``'s (the cell's configuration, traffic and
seed, one warm-up instance); then whole instances back to back for
``--seconds``, each through ``RealExecutor`` with
``RunConfig(perf_counters=True)``.  Per instance it keeps the wall, the
executor's ``PerfCounters`` (passes, waits and their timeouts, hand-offs),
the payloads' ``PayloadCounters`` (lock and device waits), the
interpreter's garbage collections (count, summed and longest pause per
generation), which hold every thread while they run, and its longest
payload calls (seconds, start after the instance's, task).  The first
``--traced`` instances run under the profiler, each in a trace of its
own, reduced by ``bench/trace.py`` (busy, idle stretches by payload and
by the program's spans) and read by ``bench/spans.py`` (the spans' four
numbers).  No check runs: this is a diagnostic, not a cell.

The whole record goes to ``--out``; the last line of stdout is a summary
with every instance longer than the median by more than ``STALL_S``.
Without a TPU it exits 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

#: an instance this much longer than the window's median is stalled
STALL_S = 0.5
#: payload calls kept per instance, longest first
LONGEST = 3


class GcPauses:
    """``gc.callbacks`` entry: per generation, the collections' count,
    summed and longest wall seconds."""

    def __init__(self):
        self.by_gen: dict = {}
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        c = self.by_gen.setdefault(str(info["generation"]), [0, 0.0, 0.0])
        c[0] += 1
        c[1] += dt
        c[2] = max(c[2], dt)


def instance(wl, inst: int) -> dict:
    """One whole instance with the executor's counters on; returns its
    wall, both counter records, its collections and its longest calls."""
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    try:
        t0, t1, _, counters = wl.run_instance(inst, perf=True)
    finally:
        gc.callbacks.remove(pauses)
    calls = sorted(((c.end - c.start, c.start - t0, f"{c.set}[{c.i}]")
                    for c in wl.spans if c.inst == inst), reverse=True)
    return dict(inst=inst, wall_s=t1 - t0, **counters, gc=pauses.by_gen,
                longest_calls=calls[:LONGEST])


def traced(wl, inst: int, options) -> dict:
    """``instance`` under the profiler, with its trace reduced."""
    import jax

    from bench import spans, trace

    trace_dir = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            out = instance(wl, inst)
        jax.profiler.stop_trace()
        pb = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
        r = trace.reduce(pb)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    named = [[name, ns / 1e9, {k: v / 1e9 for k, v in parts.items()}]
             for name, ns, parts in r.span_gaps]
    out["trace"] = dict(
        busy_s=r.busy_ns / 1e9, window_s=r.window_ns / 1e9,
        idle_s={k: v / 1e9 for k, v in r.idle_ns.items()},
        idle_gaps=[[k, v / 1e9] for k, v in r.gaps],
        span_gaps=named,
        spans=len(r.spans),
        engine_ms_per_task=spans.engine_ms_per_task(r.spans),
        task_handoff_ms=spans.task_handoff_ms(r.spans),
        lock_wait_share=spans.lock_wait_share(r.spans),
        wait_timeouts=spans.wait_timeouts(r.spans))
    return out


def run(name: str, seed: int, seconds: float, n_traced: int, *,
        require_chip: bool = True, cell: dict | None = None,
        log=None) -> dict:
    """The hunt over one window of cell ``name``; ``cell`` and
    ``require_chip`` as in ``bench.run.run``."""
    import jax

    from bench import arch, device, harness
    from bench import run as bench_run
    from repro.models.api import build_model

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    cell = cell or bench_run.load_cell(name)
    chips = cell["cell"]["chips"]
    devs = device.require(chips) if require_chip else jax.devices()[:chips]
    model = build_model(arch.of(cell["cfg"]).program_config(cell["cfg"], log))
    wl = harness.Workload(cell["cfg"], cell["traffic"], seed, model)
    wl.warm()
    setup_s = time.perf_counter() - T_PROCESS
    options = bench_run._profile_options()
    records = []
    deadline = time.perf_counter() + seconds
    inst = 1
    while time.perf_counter() < deadline:
        wl.reset()
        if inst <= n_traced:
            rec = traced(wl, inst, options)
        else:
            rec = instance(wl, inst)
        rec["traced"] = inst <= n_traced
        records.append(rec)
        log(f"instance {inst}: {rec['wall_s']:.4f} s")
        inst += 1
    walls = [r["wall_s"] for r in records]
    median = statistics.median(walls)
    stalled = [r for r in records if r["wall_s"] > median + STALL_S]
    return dict(workload=name, seed=seed, setup_s=setup_s,
                device=device.describe(devs), instances=len(records),
                median_wall_s=median, stalled=stalled, records=records)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traced", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import device
    from bench.run import CACHE

    CACHE.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = run(args.workload, args.seed, args.seconds, args.traced)
    except device.NoChip as err:
        print(f"no result: {err}", file=sys.stderr)
        return 2
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result))
    summary = {k: v for k, v in result.items() if k != "records"}
    summary["traced"] = [dict(inst=r["inst"], wall_s=r["wall_s"], **{
        k: r["trace"][k] for k in ("busy_s", "window_s", "engine_ms_per_task",
                                   "task_handoff_ms", "lock_wait_share",
                                   "wait_timeouts", "span_gaps")})
        for r in result["records"] if r["traced"]]
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
