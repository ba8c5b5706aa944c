"""A cell of the benchmark cut to a size the CPU runs in seconds: the
tiny configuration in ``data/``, the cell's traffic at small shapes, and
limits set, as the real cells' are, from readings of sound runs and of the
control at this size (``data/tiny_limits.json``)."""

import json
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"


def cell(name: str) -> dict:
    from bench.run import load_cell

    c = load_cell(name)
    c["cfg"] = json.loads((DATA / "tiny.json").read_text())
    sh = c["traffic"]["shapes"]
    c["traffic"]["shapes"] = dict(
        train_batch=2, train_seq=16, decode_batch=2, cache_len=32,
        decode_steps=min(4, sh["decode_steps"]), prefill_batch=2,
        prefill_seq=16)
    limits = json.loads((DATA / "tiny_limits.json").read_text())
    c["limits"] = {k: limits[k] for k in c["limits"]}
    return c


def run(name: str, seed: int = 3, seconds: float = 1.0) -> dict:
    from bench import run as bench_run

    return bench_run.run(name, seed, seconds, False, require_chip=False,
                         cell=cell(name), log=lambda *a: None)
