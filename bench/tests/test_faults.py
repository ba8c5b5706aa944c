"""A run whose timed path is broken underneath the harness reads as not
correct: the whole run, but for the look for a chip, at a small size.

Each fault is planted in the program (its payloads' compiled steps or
methods, or its executor), never in the harness."""

import jax
import jax.numpy as jnp
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module", autouse=True)
def compile_cache(tmp_path_factory):
    """One compile cache for the module's runs: each builds the program
    anew, and only the first should compile."""
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    yield
    jax.config.update("jax_compilation_cache_dir", old[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])


def _plant(monkeypatch, fault):
    """Break the program's payloads as they are built."""
    from repro.launch import ddmd
    from repro.runtime import TrainOptions
    from repro.runtime.steps import build_train_step

    init = ddmd.DDMDPayloads.__init__

    def broken(self, model, shapes):
        init(self, model, shapes)
        c = self.compiled
        train, prefill, decode = c["train"], c["prefill"], c["decode"]
        if fault == "state_unchanged":
            def step(state, batch):
                _, metrics = train(jax.tree.map(jnp.copy, state), batch)
                return state, metrics
            c["train"] = step
        elif fault == "half_batch":
            raw, _ = build_train_step(model,
                                      opts=TrainOptions(total_steps=100))
            c["train"] = lambda state, batch: raw(
                state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
        elif fault == "token_altered":
            def step(params, cache, tok, pos):
                nxt, logits, cache = decode(params, cache, tok, pos)
                return (nxt + 1) % model.cfg.vocab_size, logits, cache
            c["decode"] = step
        elif fault == "answer_altered":
            c["prefill"] = lambda params, batch: prefill(params,
                                                         batch)[:, ::-1]

    monkeypatch.setattr(ddmd.DDMDPayloads, "__init__", broken)


def _plant_executor(monkeypatch, fault):
    from repro.core import executor
    from repro.core.dag import DAG

    run = executor.RealExecutor.run

    def broken(self, dag, *args, **kw):
        if fault == "task_run_twice":
            next(iter(dag.nodes.values())).payload(0)
        elif fault == "dependency_ignored":
            flat = DAG()
            for ts in dag.nodes.values():
                flat.add(ts)
            dag = flat
        return run(self, dag, *args, **kw)

    monkeypatch.setattr(executor.RealExecutor, "run", broken)


def test_sound_runs_are_correct():
    res = tiny.run("ddmd.qwen2-0.5b")
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered", "answer_altered"])
def test_payload_fault_is_not_correct(monkeypatch, fault):
    _plant(monkeypatch, fault)
    res = tiny.run("ddmd.qwen2-0.5b")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["task_run_twice", "dependency_ignored"])
def test_executor_fault_is_not_correct(monkeypatch, fault):
    _plant_executor(monkeypatch, fault)
    res = tiny.run("ddmd.qwen2-0.5b")
    assert not res["correct"], res["checks"]
    assert res["checks"]["exec_faults"]["value"] > 0
