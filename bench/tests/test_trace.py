"""The trace reduction: on hand-made intervals, and on two small traces
recorded on one TPU v5e: ``data/small.xplane.pb`` (inside the window, a
prefill and a two-step decode rollout in two threads, a 20 ms pause, an
aggregation sort and one train step of qwen2-0.5b at the ddmd shapes)
and ``data/scope.xplane.pb`` with its program's HLO text
(``record_scope_trace.py``: a step under ``jax.named_scope("demo")``
beside a program span and a harness span)."""

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
SMALL = DATA / "small.xplane.pb"
SCOPE = DATA / "scope.xplane.pb"


def test_names():
    assert trace.program_name("jit_decode(13849478673689253672)") == \
        "jit_decode"
    assert trace.op_name("%decode_attention_pallas.6 = bf16[16,8,64] "
                         "custom-call(...)") == "decode_attention_pallas"
    assert trace.op_name("%fusion.93 = (f32[8]) fusion(...)") == "fusion"


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/jvp(moe)/dot_general", "moe"),
    ("jit(step)/transpose(jvp(moe))/mul", "moe"),
    ("jit(step)/while/body/attn/dot_general", "while/body/attn"),
    ("jit(step)/mul", ""),
    ("jit(step)/jvp()/reduce_sum", ""),
    ("jit(step)/transpose(jvp())/while/body/mul", "while/body"),
    ("reduce_sum", ""),
])
def test_scope_of(op_name, scope):
    assert trace.scope_of(op_name) == scope


def test_instruction_scopes_of_compiled_text():
    import jax
    import jax.numpy as jnp

    def loss(w, x):
        with jax.named_scope("moe"):
            y = jnp.tanh(x @ w)
        return jnp.sum(y * y)

    def step(w, x):
        return jax.grad(loss)(w, x)

    text = jax.jit(step).lower(jnp.ones((8, 8)),
                               jnp.ones((4, 8))).compile().as_text()
    program, scopes = trace.instruction_scopes(text)
    assert program == "jit_step"
    assert "moe" in scopes.values()
    assert set(scopes.values()) <= {"", "moe"}


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


@pytest.fixture(scope="module")
def scoped():
    return trace.reduce(str(SCOPE), [(DATA / "scope.hlo.txt").read_text()])


def test_scope_trace_device_time_by_named_scope(scoped):
    prog = "jit_demo_step"
    # the window holds the last of the three runs (the device's clock leads)
    assert scoped.programs[prog][0] == 1
    scopes = scoped.scopes[prog]
    ops, ns = scopes["demo"]
    assert ops == 2 and ns > 0.9 * scoped.busy_ns
    assert set(scopes) == {"", "demo"}
    # every op of the program counts under one scope, with its self time
    mine = [v for k, v in scoped.op_self_ns.items() if k.startswith(prog)]
    assert sum(n for _, n in scopes.values()) == pytest.approx(sum(mine))


def test_scopes_need_the_programs_text():
    assert trace.reduce(str(SCOPE)).scopes == {}
    assert trace.reduce(str(SMALL)).scopes == {}


def test_scope_trace_program_spans(scoped):
    """Only the program's span: not the window, not the harness's
    ``payload:`` span, not JAX's own host events."""
    (sp,) = scoped.spans
    assert sp.name == "demo:step" and sp.stats == {"runs": 3}
    assert {name for name, _, _ in scoped.span_gaps} <= {"demo:step", "none"}
