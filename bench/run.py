"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by
name from ``BENCHMARK.json``: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/limits/<cell>.json`` and
``bench/metrics/<metric>.py``.

Set-up (counted in ``setup_s``): find the chips, build the program's
payloads (their steps compile, or load from the compile cache in
``.jax_cache/`` of the checkout), make the state from the seed, and run
one whole instance, which also warms every small program the payloads
dispatch.  Then whole instances run back to back for ``--seconds``.
With ``--trace 1`` the profiler records the window's first instance, the
executor counts its own work (``PerfCounters``), and the run reports the
per-layer metrics read from that trace, the programs' HLO text (kept at
set-up) and that instance's counters; otherwise the end-to-end metrics.
The result's ``window`` holds each instance's counters.  Last, the
check: the executor's schedule, and what the timed path produced against
the plain reference (``bench/check.py``), run once the program's state
is freed.

The last line of stdout is the result, as JSON; the numbers compared
and their limits are also the last lines of stderr.  Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE = ROOT / ".jax_cache"


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic, limits and the
    metrics it reports, as ``BENCHMARK.json`` names them."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def reports(m):
        return name in m.get("workloads", [name])

    return dict(
        cell=cell,
        cfg=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{cell['traffic']}.json")
            .read_text()),
        limits=json.loads(
            (root / "bench" / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if reports(m)],
        per_layer=[m for m in spec["per_layer"]
                   if name in m.get("workloads", [])],
    )


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # Python calls: large and not read
    opts.host_tracer_level = 1        # the harness's annotations
    return opts


def run(name: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, require_chip: bool = True, cell: dict | None = None,
        log=None) -> dict:
    """One run of cell ``name``; returns the result line's object (with
    the numbers compared under ``checks``).  ``cell`` replaces what
    ``load_cell`` would find (tests pass a small one); ``require_chip``
    False skips only the look for a TPU."""
    import jax

    from bench import arch, check, device, harness, trace as tr
    from bench.readers import Context
    from repro.models.api import build_model

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    cell = cell or load_cell(name, root)
    cfg, traffic = cell["cfg"], cell["traffic"]
    chips = cell["cell"]["chips"]
    devs = device.require(chips) if require_chip else jax.devices()[:chips]
    peaks = device.PEAKS.get(devs[0].device_kind)

    model = build_model(arch.of(cfg).program_config(cfg, log))
    wl = harness.Workload(cfg, traffic, seed, model)
    warm_record = wl.warm()
    setup_s = time.perf_counter() - T_PROCESS
    # the traced run's set-up: after ``setup_s``, before the window
    hlo_texts = wl.hlo_texts() if trace else ()

    # programs made inside the window: compiled, or loaded from the cache
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(ev)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    jax.monitoring.register_event_listener(
        lambda ev, **kw: compiles.append(ev)
        if ev == "/jax/compilation_cache/cache_hits" else None)
    trace_dir = tempfile.mkdtemp() if trace else None
    win = wl.window(seconds, trace_dir, _profile_options())
    in_window = len(compiles)
    wm = win["metrics"]
    dev_info = device.describe(devs)

    result: dict = dict(correct=False, attempted=0, failed=0, metrics={},
                        device=dev_info)
    if trace:
        pb = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        reduced = tr.reduce(pb[0], hlo_texts)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Context(trace=reduced, cfg=cfg, traffic=traffic, peaks=peaks,
                      counters=win["counters"][0])
        for m in cell["per_layer"]:
            v = _reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = dict(value=v, unit=m["unit"])
        dev_info["busy_s"] = reduced.busy_ns / 1e9
        dev_info["window_s"] = reduced.window_ns / 1e9
        result["breakdown"] = tr.breakdown(reduced)
    else:
        for m in cell["end_to_end"]:
            v = setup_s if m["name"] == "setup_s" else wm.get(m["name"])
            if v is not None:
                result["metrics"][m["name"]] = dict(value=v, unit=m["unit"])

    # the check: schedule and inputs now, outputs once the state is freed
    records = [warm_record] + ([win["kept"]] if win["kept"] else [])
    faults = check.exec_faults(wl, records)
    result["attempted"] = win["attempted"]
    result["failed"] = faults
    del wl, model
    gc.collect()
    numbers = dict(exec_faults=float(faults))
    numbers.update(check.compare(cfg, traffic, seed, records))
    limits = cell["limits"]
    shown = {k: dict(value=numbers[k], limit=limits[k]) for k in limits}
    result["correct"] = (wm["instances"] > 0 and check.verdict(
        {k: numbers[k] for k in limits}, limits))
    result["window"] = dict(wm, compiles_in_window=in_window,
                            instance_walls=win["walls"],
                            counters=win["counters"])
    result["checks"] = shown
    for k, v in shown.items():
        log(f"check {k} = {v['value']!r} (limit {v['limit']!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    CACHE.mkdir(exist_ok=True)       # JAX writes into it, never makes it
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    # every program, however quick to compile, so that set-up after the
    # first run of a checkout loads and never compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import device

    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except device.NoChip as err:
        print(f"no result: {err}", file=sys.stderr)
        return 2
    if not all(math.isfinite(m["value"]) for m in result["metrics"].values()):
        print("no result: a metric is not finite", file=sys.stderr)
        return 1
    checks = result.pop("checks")
    result["checks"] = checks          # the numbers compared come last
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
