"""The program-span reduction (``bench/spans.py``) on hand-made spans and
on the recorded v5e trace, and the hunt's per-instance counters on the
tiny cell."""

from pathlib import Path

import pytest

from bench import spans, trace
from bench.spans import HostSpan

SMALL = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def _span(name, start, end, thread, **stats):
    return HostSpan(start, end, name, thread, stats)


def test_idle_stretches_named_by_innermost_span():
    # window 0..100; busy 0..10, 30..40, 90..100.  Thread 0 dispatches
    # (pass 10..15, then waits); thread 1 runs one rollout in a task
    busy = [(0, 10), (30, 40), (90, 100)]
    sp = [_span("exec:pass", 10, 15, 0), _span("exec:wait", 15, 90, 0),
          _span("exec:task", 12, 95, 1), _span("ddmd:simulation", 13, 94, 1),
          _span("ddmd:lock", 20, 25, 1), _span("ddmd:block", 50, 94, 1)]
    gaps = spans.named_gaps(busy, (0, 100), sp)
    # 40..90: host work 40..50, then the device wait
    assert gaps[0] == ("ddmd:block", 50, {"ddmd:simulation": 10,
                                          "ddmd:block": 40})
    # 10..30: the pass 10..13, host work 13..20 and 25..30, the lock 20..25
    assert gaps[1] == ("ddmd:simulation", 20, {"exec:pass": 3,
                                               "ddmd:simulation": 12,
                                               "ddmd:lock": 5})


@pytest.mark.parametrize("a,b,name", [
    ("ddmd:lock", "ddmd:training", "ddmd:training"),
    ("ddmd:training", "ddmd:simulation", "ddmd:simulation+training"),
    ("exec:pass", "ddmd:lock", "exec:pass"),
    ("exec:predict", "exec:task", "exec:predict"),
    ("exec:task", "ddmd:lock", "exec:task"),
    ("ddmd:lock", "ddmd:block", "ddmd:lock"),
    ("exec:wait", "ddmd:block", "ddmd:block"),
])
def test_precedence_when_threads_disagree(a, b, name):
    sp = [_span(a, 0, 10, 0), _span(b, 0, 10, 1)]
    assert spans.named_gaps([], (0, 10), sp) == [(name, 10, {name: 10})]


@pytest.mark.parametrize("name,program", [
    ("exec:pass", True), ("ddmd:simulation", True), ("demo:step", True),
    ("moe_layer:route", True), ("bench:window", False),
    ("payload:simulation", False), ("PjitFunction(decode)", False),
    ("Foo::Bar", False), ("tsl::profiler", False), ("ExecuteOnLocalDevices", False),
])
def test_program_spans_have_the_programs_form(name, program):
    assert spans.is_program_span(name) is program


def test_other_program_spans_rank_as_host_work():
    sp = [_span("moe:route", 0, 10, 0), _span("ddmd:lock", 0, 10, 1),
          _span("exec:task", 0, 10, 2)]
    assert spans.named_gaps([], (0, 10), sp[:2]) == [
        ("moe:route", 10, {"moe:route": 10})]
    assert spans.named_gaps([], (0, 10), sp[1:] + sp[:1]) == [
        ("exec:task", 10, {"exec:task": 10})]


def test_no_open_span_is_none():
    sp = [_span("exec:wait", 0, 4, 0)]
    assert spans.named_gaps([(6, 10)], (0, 10), sp) == [
        ("exec:wait", 6, {"exec:wait": 4, "none": 2})]


def test_numbers_read_from_spans():
    sp = [_span("exec:pass", 0, 2e6, 0, started=2),
          _span("exec:predict", 2e6, 3e6, 0),
          _span("exec:pass", 5e6, 6e6, 0, started=0),
          _span("exec:wait", 3e6, 5e6, 0, timeout=0),
          _span("exec:wait", 6e6, 9e6, 0, timeout=1),
          _span("exec:task", 0, 9e6, 1, task="a[0]", handoff_us=100.0),
          _span("exec:task", 0, 9e6, 2, task="a[1]", handoff_us=300.0),
          _span("ddmd:simulation", 0, 8e6, 1), _span("ddmd:lock", 1, 2e6, 1),
          _span("ddmd:inference", 0, 2e6, 2), _span("ddmd:block", 0, 1e6, 2)]
    assert spans.engine_ms_per_task(sp) == pytest.approx(2.0)
    assert spans.task_handoff_ms(sp) == pytest.approx(0.2)
    assert spans.lock_wait_share(sp) == pytest.approx(
        100 * (2e6 - 1) / 10e6)
    assert spans.wait_timeouts(sp) == 1


def test_small_trace_has_no_program_spans():
    """The recorded trace predates the program's spans: the numbers read
    nothing, every idle stretch is ``none``, and the stretches are the
    ones found by host state."""
    r = trace.reduce(str(SMALL))
    assert r.spans == []
    assert spans.engine_ms_per_task(r.spans) is None
    assert spans.task_handoff_ms(r.spans) is None
    assert spans.lock_wait_share(r.spans) is None
    assert spans.wait_timeouts(r.spans) == 0
    assert {name for name, _, _ in r.span_gaps} == {"none"}
    assert [ns for _, ns, _ in r.span_gaps] == [ns for _, ns in r.gaps]


def test_hunt_keeps_counters_per_instance(tmp_path):
    import jax

    from bench import hunt
    from bench.tests import tiny

    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        cell = tiny.cell("ddmd.qwen2-0.5b")
        out = hunt.run("ddmd.qwen2-0.5b", 3, 0.5, 0, require_chip=False,
                       cell=cell, log=lambda *a: None)
    finally:
        jax.config.update("jax_compilation_cache_dir", old[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old[1])
    assert out["instances"] == len(out["records"]) >= 1
    steps = cell["traffic"]["shapes"]["decode_steps"]
    for rec in out["records"]:
        assert rec["perf"]["passes"] > 0 and rec["perf"]["starts"] == 48
        assert rec["perf"]["wait_timeouts"] == 0
        assert rec["payload"]["lock_acquires"] == 6 * 3 * steps + 3 + 18
        assert not rec["traced"]
        assert all(n >= 1 and 0.0 <= top <= total
                   for n, total, top in rec["gc"].values())
        (dur, at, task), *_ = rec["longest_calls"]
        assert 0.0 < dur < rec["wall_s"] and at >= 0.0 and "[" in task


def test_traced_run_keeps_counters_per_instance(monkeypatch):
    """A ``--trace 1`` run of the tiny cell on the CPU: every instance of
    the window reports its executor and payload counters, the readers'
    ``Context`` carries the traced instance's, and the reduction is given
    every program's HLO text.  The CPU's trace has no TPU plane, so the
    recorded v5e trace stands in for it."""
    from bench import run as bench_run
    from bench.tests import tiny

    texts, contexts = [], []
    small = trace.reduce(str(SMALL))
    monkeypatch.setattr(trace, "reduce",
                        lambda path, hlo_texts=(): (texts.append(hlo_texts),
                                                    small)[1])
    monkeypatch.setattr(bench_run, "_reader",
                        lambda name: lambda ctx: contexts.append(ctx))
    cell = tiny.cell("ddmd.qwen2-0.5b")
    res = bench_run.run("ddmd.qwen2-0.5b", 3, 0.5, True, require_chip=False,
                        cell=cell, log=lambda *a: None)
    steps = cell["traffic"]["shapes"]["decode_steps"]
    counters = res["window"]["counters"]
    assert len(counters) == len(res["window"]["instance_walls"]) >= 1
    for c in counters:
        assert c["perf"]["starts"] == 48 and c["perf"]["passes"] > 0
        assert c["payload"]["lock_acquires"] == 6 * 3 * steps + 3 + 18
    assert len(contexts) == len(cell["per_layer"])
    assert all(ctx.counters == counters[0] for ctx in contexts)
    (given,) = texts
    assert sorted(t.split()[1].rstrip(",") for t in given) == [
        "jit_decode", "jit_prefill", "jit_serving_params", "jit_train_step"]
    assert [name for name, _ in res["breakdown"]["span_gaps"]] == [
        name for name, _, _ in small.span_gaps]
