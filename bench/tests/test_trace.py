"""The trace reduction: on hand-made intervals, and on a small trace
recorded on one TPU v5e (``data/small.xplane.pb``: inside the window, a
prefill and a two-step decode rollout in two threads, a 20 ms pause, an
aggregation sort and one train step of qwen2-0.5b at the ddmd shapes)."""

from pathlib import Path

import pytest

from bench import trace

SMALL = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_names():
    assert trace.program_name("jit_decode(13849478673689253672)") == \
        "jit_decode"
    assert trace.op_name("%decode_attention_pallas.6 = bf16[16,8,64] "
                         "custom-call(...)") == "decode_attention_pallas"
    assert trace.op_name("%fusion.93 = (f32[8]) fusion(...)") == "fusion"


def test_idle_split_by_host_state():
    # window 0..100; busy 0..10, 30..40, 90..100; one payload 20..50
    busy = [(0, 10), (30, 40), (90, 100)]
    by_state, gaps = trace._idle(busy, (0, 100),
                                 [(20, 50, "simulation")])
    # gap 10..30: 10 dispatch, 10 in the payload; gap 40..90: 10 in the
    # payload, 40 dispatch
    assert by_state == {"dispatch": 50, "payload:simulation": 20}
    assert gaps == [("dispatch", 50), ("dispatch", 20)]


def test_idle_names_overlapping_kinds():
    by_state, _ = trace._idle([], (0, 10), [(0, 6, "training"),
                                            (4, 10, "inference")])
    assert by_state == {"payload:training": 4,
                        "payload:inference+training": 2,
                        "payload:inference": 4}


@pytest.fixture(scope="module")
def small():
    return trace.reduce(str(SMALL))


def test_small_trace_programs(small):
    assert small.programs["jit_prefill"][0] == 1
    assert small.programs["jit_decode"][0] == 2
    assert small.programs["jit_train_step"][0] == 1


def test_small_trace_kernels_by_program(small):
    decode = small.kernels["decode_attention_pallas"]
    assert set(decode) == {"jit_decode"}
    assert decode["jit_decode"][0] == 2 * 24          # two steps, 24 layers
    flash = small.kernels["flash_attention_pallas"]
    assert flash["jit_prefill"][0] == 24
    assert flash["jit_train_step"][0] >= 24


def test_small_trace_busy_and_idle(small):
    idle = sum(small.idle_ns.values())
    assert 0 < small.busy_ns < small.window_ns
    assert abs(small.busy_ns + idle - small.window_ns) < 1e3
    # the 20 ms pause between the threads and the sort, with no payload open
    assert small.idle_ns["dispatch"] >= 15e6
    assert any(k.startswith("payload:") for k in small.idle_ns)


def test_breakdown_shape(small):
    b = trace.breakdown(small)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"])
