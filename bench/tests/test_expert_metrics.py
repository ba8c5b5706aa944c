"""The expert cell's per-layer readers (``moe_decode_ms``,
``mla_decode_ms``, ``moe_train_share``, ``expert_load_ratio``) on
hand-made reduced traces and counters, and on a program that opens no such
scope and keeps no such counter, as a dense model (or the parent commit)
does: they read nothing and do not raise."""

import types

import pytest

from bench import run as bench_run
from bench.arch import DECODE, TRAIN
from bench.readers import Context


def _ctx(scopes, programs, counters):
    trace = types.SimpleNamespace(scopes=scopes, programs=programs)
    return Context(trace=trace, cfg={}, traffic={}, peaks={},
                   counters=counters)


FULL = _ctx(
    scopes={DECODE: {"while/body/closed_call/moe": [40, 6e6],
                     "moe": [2, 1e6],
                     "mla/decode_attention_pallas/decode/while/body": [9, 2e6],
                     "while/body/closed_call/mla": [10, 1e6],
                     "": [100, 5e6]},
            TRAIN: {"checkpoint/while/body/closed_call/moe": [50, 30e6],
                    "checkpoint/rematted_computation/moe": [5, 10e6],
                    "checkpoint/mla": [9, 20e6]}},
    programs={DECODE: [4, 16e6], TRAIN: [2, 200e6]},
    counters={"payload": {"expert_tokens": [10.0, 30.0, 20.0, 20.0]}})

BARE = _ctx(scopes={DECODE: {"while/body": [5, 1e6]}},
            programs={DECODE: [4, 16e6], TRAIN: [2, 200e6]},
            counters={"payload": {"lock_acquires": 3}})


@pytest.mark.parametrize("name,want", [
    ("moe_decode_ms", 7e6 / 4 / 1e6),
    ("mla_decode_ms", 3e6 / 4 / 1e6),
    ("moe_train_share", 100.0 * 40e6 / 200e6),
    ("expert_load_ratio", 30.0 / 20.0),
])
def test_reads(name, want):
    assert bench_run._reader(name)(FULL) == pytest.approx(want)
    assert bench_run._reader(name)(BARE) is None



def test_listed_for_the_expert_cell_alone():
    """The four metrics are the expert cell's alone; the cell reports the
    end-to-end metric they move and the accepted per-layer metrics."""
    cell = bench_run.load_cell("ddmd.moonlight-16b-a3b")
    names = {m["name"] for m in cell["per_layer"]}
    new = {"moe_decode_ms", "mla_decode_ms", "moe_train_share",
           "expert_load_ratio"}
    assert new <= names
    assert {"decode_step_ms", "train_step_ms", "step_mfu"} <= names
    assert "makespan_s" in {m["name"] for m in cell["end_to_end"]}
    for dense in ("ddmd.qwen2-0.5b", "ddmd.h2o-danube-1.8b"):
        other = {m["name"] for m in bench_run.load_cell(dense)["per_layer"]}
        assert not new & other
