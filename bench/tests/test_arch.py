"""A second architecture comes to the benchmark as new files alone: the
test's own module ``qknorm_arch.py`` (per-head q/k norm, partial rotary)
and its configuration ``data/tiny_qknorm.json``.  The whole run, but for
the look for a chip, reads correct at the tiny size, and reads not
correct where the program ignores its queries' norm."""

import json
from pathlib import Path

from bench import run as bench_run
from bench.tests import tiny

CFG = Path(__file__).resolve().parent / "data" / "tiny_qknorm.json"


def _run() -> dict:
    cell = tiny.cell("ddmd.qwen2-0.5b")
    cell["cfg"] = json.loads(CFG.read_text())
    return bench_run.run("ddmd.qwen2-0.5b", 3, 1.0, False,
                         require_chip=False, cell=cell, log=lambda *a: None)


def test_second_architecture_is_correct():
    res = _run()
    assert res["correct"], res["checks"]


def test_ignored_q_norm_is_not_correct(monkeypatch):
    from repro.models import attention

    project = attention._project_qkv

    def without_q_norm(p, *args, **kw):
        return project({k: v for k, v in p.items() if k != "q_norm"},
                       *args, **kw)

    monkeypatch.setattr(attention, "_project_qkv", without_q_norm)
    res = _run()
    assert not res["correct"], res["checks"]
