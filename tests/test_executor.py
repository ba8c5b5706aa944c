"""Real concurrent executor: actual asynchronous execution on this host."""

import threading

import jax
import jax.numpy as jnp
import pytest

from repro.core import (DAG, PoolSpec, NodeSpec, RealExecutor, RunConfig,
                        TaskSet, cdg_dag, deepdrivemd_dag)

SMALL_POOL = PoolSpec("local", num_nodes=1, node=NodeSpec(cpus=8, gpus=4),
                      oversubscribe_cpus=True)


def _scaled(dag, scale=2e-4):
    g = dag.copy()
    for name, ts in dag.nodes.items():
        g.replace(name, tx_mean=ts.tx_mean * scale / 2e-4 * 2e-4,
                  tx_sigma=0.0)
    return g


def test_async_faster_than_sequential_wallclock():
    # two independent chains of sleeps: async must overlap them
    g = DAG()
    g.add(TaskSet("A", 2, 1, 1, tx_mean=0.15, tx_sigma=0.0))
    g.add(TaskSet("B", 2, 1, 1, tx_mean=0.15, tx_sigma=0.0))
    ex = RealExecutor(SMALL_POOL, tx_scale=1.0)
    ra = ex.run(g, "async")
    rs = ex.run(g, "sequential", sequential_stage_groups=[["A"], ["B"]])
    assert ra.makespan < rs.makespan * 0.8
    assert ra.tasks_total == rs.tasks_total == 4


def test_dependencies_respected_wallclock():
    g = DAG()
    g.add(TaskSet("A", 1, 1, 0, tx_mean=0.05, tx_sigma=0.0))
    g.add(TaskSet("B", 1, 1, 0, tx_mean=0.05, tx_sigma=0.0))
    g.add_edge("A", "B")
    res = RealExecutor(SMALL_POOL).run(g, "async")
    rec = {r.set_name: r for r in res.records}
    assert rec["B"].start >= rec["A"].end - 1e-3


def test_jax_payloads_execute():
    """Heterogeneous payloads: a jitted train-ish step and an inference-ish
    step genuinely run and produce finite numbers."""
    results = {}
    lock = threading.Lock()

    @jax.jit
    def heavy(x):
        return jnp.tanh(x @ x.T).sum()

    def sim_payload(i):
        v = float(heavy(jnp.ones((64, 64)) * (i + 1)))
        with lock:
            results[("sim", i)] = v

    def ml_payload(i):
        v = float(heavy(jnp.eye(32)))
        with lock:
            results[("ml", i)] = v

    g = DAG()
    g.add(TaskSet("sim", 3, 1, 1, tx_mean=0.0, payload=sim_payload,
                  kind="simulation"))
    g.add(TaskSet("ml", 2, 1, 1, tx_mean=0.0, payload=ml_payload,
                  kind="training"))
    g.add_edge("sim", "ml")
    res = RealExecutor(SMALL_POOL).run(g, "async")
    assert res.tasks_total == 5
    assert len(results) == 5
    assert all(jnp.isfinite(v) for v in results.values())
    # dependency: every ml record starts after all sim records end
    sim_end = max(r.end for r in res.records if r.set_name == "sim")
    ml_start = min(r.start for r in res.records if r.set_name == "ml")
    assert ml_start >= sim_end - 1e-3


def test_gpu_slots_limit_concurrency():
    """4 GPU slots, 8 single-GPU tasks of 0.1 s -> at least two waves."""
    g = DAG()
    g.add(TaskSet("T", 8, 1, 1, tx_mean=0.1, tx_sigma=0.0))
    res = RealExecutor(SMALL_POOL).run(g, "async")
    assert res.makespan >= 0.19


def test_ddmd_shape_runs_at_laptop_scale():
    dd = _scaled(deepdrivemd_dag(2))
    for name, ts in dd.nodes.items():
        dd.replace(name, tx_mean=0.02, num_tasks=min(ts.num_tasks, 6))
    ex = RealExecutor(SMALL_POOL)
    ra = ex.run(dd, "async")
    rs = ex.run(dd, "sequential")
    assert ra.tasks_total == rs.tasks_total
    assert ra.makespan <= rs.makespan * 1.05


def test_task_level_executor():
    g = cdg_dag("c-DG2")
    for name, ts in g.nodes.items():
        g.replace(name, tx_mean=0.01, num_tasks=min(ts.num_tasks, 4),
                  tx_sigma=0.0)
    res = RealExecutor(SMALL_POOL).run(g, "async", task_level=True)
    assert res.tasks_total == sum(ts.num_tasks for ts in g.nodes.values())


def test_executor_perf_counters():
    """``perf_counters`` fills the executor's ``PerfCounters``: every task
    attempt is counted at its hand-off, and no wait runs out its timeout
    in a run far shorter than it."""
    g = DAG()
    g.add(TaskSet("A", 3, 1, 1, tx_mean=0.03, tx_sigma=0.0))
    g.add(TaskSet("B", 2, 1, 1, tx_mean=0.03, tx_sigma=0.0))
    g.add_edge("A", "B")
    ex = RealExecutor(SMALL_POOL)
    res = ex.run(g, "async", config=RunConfig(perf_counters=True))
    p = res.perf
    assert p is not None
    assert p.passes > 0 and p.starts == res.tasks_total == 5
    assert 0.0 <= p.handoff_max_s and p.handoff_max_s <= p.handoff_s
    assert p.wait_timeouts == 0 and p.wait_s > 0.0
    assert 0.0 < p.engine_s + p.predict_s + p.wait_s <= p.total_s
    assert p.predicts <= len(res.predictions)
    assert ex.run(g, "async").perf is None


def test_executor_spans_in_profiler_trace(tmp_path):
    """The dispatcher's and workers' spans land in a profiler trace with
    their stats, and each ``exec:task`` encloses its payload's span."""
    from jax.profiler import ProfileData, TraceAnnotation

    def payload(i):
        with TraceAnnotation("test:payload"):
            jnp.ones(8).block_until_ready()

    g = DAG()
    g.add(TaskSet("A", 2, 1, 1, tx_mean=0.0, payload=payload))
    g.add(TaskSet("B", 1, 1, 1, tx_mean=0.0, payload=payload))
    g.add_edge("A", "B")
    jax.profiler.start_trace(str(tmp_path))
    try:
        RealExecutor(SMALL_POOL).run(g, "async")
    finally:
        jax.profiler.stop_trace()
    (pb,) = tmp_path.glob("**/*.xplane.pb")
    host = ProfileData.from_file(str(pb)).find_plane_with_name("/host:CPU")
    by_line = [[(e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats)) for e in line.events] for line in host.lines]
    events = [ev for line in by_line for ev in line]
    names = {ev[0] for ev in events}
    assert {"exec:pass", "exec:wait", "exec:predict", "exec:task"} <= names
    tasks = [ev for ev in events if ev[0] == "exec:task"]
    assert sorted(ev[3]["task"] for ev in tasks) == ["A[0]", "A[1]", "B[0]"]
    assert all(ev[3]["handoff_us"] >= 0 for ev in tasks)
    assert all(ev[3]["timeout"] == 0 for ev in events
               if ev[0] == "exec:wait")
    for line in by_line:
        for name, s, e, _ in line:
            if name == "test:payload":
                assert any(n == "exec:task" and ts <= s and e <= te
                           for n, ts, te, _ in line)
    assert sum(ev[0] == "test:payload" for ev in events) == 3


def test_span_without_jax(monkeypatch):
    """Where JAX cannot be imported a span is a no-op that still takes
    stats, so ``core`` runs without JAX."""
    import sys

    from repro.core import tracing

    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    tracing._annotation.cache_clear()
    try:
        with tracing.span("exec:wait", timeout=0) as s:
            s.set_metadata(timeout=1)
        assert isinstance(s, tracing._NoSpan)
    finally:
        tracing._annotation.cache_clear()


@pytest.mark.parametrize("mode", ["sequential", "async"])
def test_raising_payload_ends_run(mode):
    """A payload that raises ends the run: ``run()`` re-raises it with the
    task's name and index instead of waiting for a completion that never
    comes."""
    from repro.core.executor import PayloadError

    def boom(i):
        if i == 1:
            raise ValueError("payload exploded")

    g = DAG()
    g.add(TaskSet("A", 3, 1, 1, tx_mean=0.0, payload=boom))
    g.add(TaskSet("B", 2, 1, 1, tx_mean=0.0, payload=lambda i: None))
    g.add_edge("A", "B")
    out = {}

    def run():
        try:
            RealExecutor(SMALL_POOL).run(g, mode)
        except BaseException as err:  # noqa: BLE001 — handed to the test
            out["err"] = err

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive(), "run() hung on a raising payload"
    err = out.get("err")
    assert isinstance(err, PayloadError), err
    assert err.task == ("A", 1)
    assert isinstance(err.__cause__, ValueError)
    assert "A[1]" in str(err)


@pytest.fixture(scope="module")
def ddmd_payloads():
    from repro.configs import get_config
    from repro.launch import ddmd
    from repro.models.api import build_model
    return ddmd.DDMDPayloads(build_model(get_config("qwen2-0.5b").reduced()))


@pytest.mark.parametrize("mode", ["sequential", "async"])
def test_ddmd_real_payloads(ddmd_payloads, mode):
    """DeepDriveMD with real model payloads sharing one (donated) train
    state: every task runs, every loss and logit is finite."""
    from repro.launch import ddmd
    p = ddmd_payloads
    n_loss, n_logits = len(p.losses), len(p.logits_finite)
    p.counters = ddmd.PayloadCounters()
    res, n_tasks = ddmd.run(p, mode)
    assert res.tasks_total == n_tasks == 48
    assert len(p.losses) - n_loss == 3
    assert len(p.logits_finite) - n_logits == 3 * (6 + 6)
    assert all(bool(jnp.isfinite(x)) for x in p.losses)
    assert all(bool(x) for x in p.logits_finite)
    # one lock acquire per decode step, train step and prefill
    c = p.counters
    assert c.lock_acquires == 6 * 3 * p.shapes.decode_steps + 3 + 18
    assert 0.0 <= c.lock_wait_max_s <= c.lock_wait_s
    assert 0.0 < c.block_max_s <= c.block_s


@pytest.mark.parametrize("step,module", [("train", "jit_train_step"),
                                         ("prefill", "jit_prefill"),
                                         ("decode", "jit_decode")])
def test_step_program_names(ddmd_payloads, step, module):
    """The device trace names each run of a step by its HLO module, and
    the benchmark's per-layer readers find the steps by these names."""
    text = ddmd_payloads.compiled[step].as_text()
    assert text.split(",", 1)[0] == f"HloModule {module}"


def test_compile_cache_dir(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is where JAX writes; unset,
    the cache goes to one fixed, git-ignored directory of the checkout."""
    import os
    import pathlib
    import subprocess
    import sys

    from repro.launch.compile_cache import CHECKOUT_CACHE, use_compile_cache

    root = pathlib.Path(__file__).resolve().parents[1]
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = use_compile_cache()
        assert first == use_compile_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert CHECKOUT_CACHE.name + "/" in (
            root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

    prog = ("import jax\n"
            "from repro.launch.compile_cache import use_compile_cache\n"
            "use_compile_cache()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(2.0))\n")
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=120,
        env={**os.environ, "PYTHONPATH": str(root / "src"),
             "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert out.returncode == 0, out.stderr[-2000:]
    assert any(tmp_path.iterdir()), "nothing was cached"
