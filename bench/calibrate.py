"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python bench/calibrate.py --workload <cell> --seeds 101-112 \\
        --control 101,102,103 --seconds 8 --out <file.jsonl>

One process, one build of the program's payloads.  For each seed it runs
what ``bench/run.py`` runs (the warm-up instance, then whole instances
for ``--seconds``) and writes the numbers the check compares: the
program's, whose largest over the seeds is each limit's lower reading.
For the ``--control`` seeds it also writes

- ``control``: the reference at int8 weights with bfloat16 activations
  put in the program's place, on the same inputs and tokens;
- ``faults``: the numbers when the timed path is broken: the train step
  leaves the state unchanged (its parameters' change reads nought), takes
  half of its batch, a served token or a prefill answer is altered.

Each of these also gets the verdict that ``bench/check.py`` gives it
against the cell's limits (``*_correct``; the numbers it has no reading
of are left out): a control or a fault has to read as not correct.  A
limit's upper reading is the smallest control reading that is three
times the lower reading or more, or a fault's that is ten times or more.
Needs the chip, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def altered(rec, vocab: int):
    """``rec`` with every served token and every answer changed where it
    was produced."""
    import numpy as np

    out = copy.copy(rec)
    out.decode = {k: [(v, t, p, (np.asarray(n) + 1) % vocab,
                       None if lg is None else np.asarray(lg)[:, ::-1])
                      for v, t, p, n, lg in steps]
                  for k, steps in rec.decode.items()}
    out.prefill = [(v, i, t, np.asarray(lg)[:, ::-1])
                   for v, i, t, lg in rec.prefill]
    return out


def calibrate(cell: dict, seeds: list[int], control: set, seconds: float,
              require_chip: bool = True):
    """Yield one line of readings per seed (see the module's doc)."""
    import gc

    import numpy as np

    from bench import arch, check, device, harness
    from repro.models.api import build_model

    cfg, traffic, limits = cell["cfg"], cell["traffic"], cell["limits"]

    def verdict(numbers: dict) -> bool:
        return check.verdict({k: numbers[k] for k in limits if k in numbers},
                             limits)

    if require_chip:
        device.require(cell["cell"]["chips"])
    wl = harness.Workload(cfg, traffic, seeds[0],
                          build_model(arch.of(cfg).program_config(cfg)))
    for n, seed in enumerate(seeds):
        if n:
            wl.reseed(seed)
        warm = wl.warm()
        win = wl.window(seconds)
        records = [warm] + ([win["kept"]] if win["kept"] else [])
        line = dict(seed=seed, instances=win["metrics"]["instances"],
                    program=dict(exec_faults=check.exec_faults(wl, records)))
        wl.payloads.state = None          # the reference needs the room
        gc.collect()
        line["program"].update(check.compare(cfg, traffic, seed, records))
        if seed in control:
            line["control"] = check.compare(cfg, traffic, seed, records,
                                            stand_in="int8")
            faults = dict(answers_altered=check.compare(
                cfg, traffic, seed,
                [altered(r, cfg["vocab_size"]) for r in records]))
            if warm.train:
                still = dataclasses.replace(
                    warm, change_norms=np.zeros_like(
                        np.asarray(warm.change_norms)))
                faults["state_unchanged"] = check.compare(cfg, traffic, seed,
                                                          [still])
                faults["half_batch"] = check.half_batch(cfg, traffic, seed,
                                                        warm)
            line["faults"] = faults
            line["control_correct"] = verdict(line["control"])
            line["faults_correct"] = {k: verdict(v)
                                      for k, v in faults.items()}
        line["program_correct"] = verdict(line["program"])
        yield line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.run import CACHE, load_cell

    CACHE.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    control = set(_seeds(args.control)) if args.control else set()
    with open(args.out, "a") as out:
        for line in calibrate(load_cell(args.workload), _seeds(args.seeds),
                              control, args.seconds):
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
