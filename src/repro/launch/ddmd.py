"""DeepDriveMD with real JAX payloads (the paper's §6.1 experiment, with
jitted model steps in place of ``stress``).

Task types, all real work on one model:
  simulation   autoregressive decode rollout (MD-like trajectory producer)
  aggregation  reduction over produced samples
  training     one train step of the shared train state
  inference    batched prefill scoring candidate sequences

Every payload reads one parameter tree, the train state's.  The train
step donates that state, so each payload dispatches under one lock and
reads the current params there; the device runs dispatched steps in
order, so a read dispatched before a donation completes before it.

``run`` drives the workflow through ``RealExecutor`` with the same
(cpus, gpus) accounting as the paper's middleware: sequential mode
barriers each stage, async mode staggers the iterations.
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp

from repro.core import RealExecutor, RunConfig, deepdrivemd_dag
from repro.core.executor import ExecResult
from repro.core.resources import NodeSpec, PoolSpec
from repro.core.workflow import ddmd_sequential_stage_groups
from repro.models.api import Model
from repro.models.params import init_params
from repro.runtime import TrainOptions
from repro.runtime.steps import (build_decode_step, build_prefill_step,
                                 build_train_step, make_train_state)

#: scaled-down task counts (Table 1's stage structure; zero modelled TX,
#: the payloads take the time they take)
TABLE = dict(
    simulation=dict(cpus=1, gpus=1, n=6, tx=0.0),
    aggregation=dict(cpus=2, gpus=0, n=3, tx=0.0),
    training=dict(cpus=1, gpus=1, n=1, tx=0.0),
    inference=dict(cpus=1, gpus=1, n=6, tx=0.0),
)

#: one host: up to four accelerator tasks in flight at once
POOL = PoolSpec("host", num_nodes=1, node=NodeSpec(cpus=8, gpus=4))


@dataclasses.dataclass(frozen=True)
class PayloadShapes:
    train_batch: int = 2
    train_seq: int = 32
    decode_batch: int = 2
    cache_len: int = 64
    decode_steps: int = 8
    prefill_batch: int = 2
    prefill_seq: int = 32


class DDMDPayloads:
    """The four payloads over one model, its steps compiled ahead of time
    at ``shapes`` (``compiled`` keeps them for memory and HLO checks).

    ``losses`` and ``logits_finite`` collect what each call produced, as
    device scalars, for the caller to check after a run."""

    def __init__(self, model: Model, shapes: PayloadShapes = PayloadShapes()):
        self.model = model
        self.shapes = shapes
        self.state = make_train_state(model, jax.random.PRNGKey(0))
        self._lock = threading.Lock()
        self.losses: list[jax.Array] = []
        self.logits_finite: list[jax.Array] = []
        s = shapes
        train, _ = build_train_step(model, opts=TrainOptions(total_steps=100))
        prefill, _ = build_prefill_step(model)
        decode, _ = build_decode_step(model, batch=s.decode_batch,
                                      s_max=s.cache_len)
        tok = jnp.zeros((s.decode_batch, 1), jnp.int32)
        pos = jnp.zeros((s.decode_batch,), jnp.int32)
        self.compiled = dict(
            train=train.lower(self.state, self._train_batch(0)).compile(),
            prefill=prefill.lower(self.state.params,
                                  self._prefill_batch(0)).compile(),
            decode=decode.lower(self.state.params, self._cache(),
                                tok, pos).compile(),
        )

    def _train_batch(self, i: int):
        return self.model.make_batch(jax.random.PRNGKey(100 + i),
                                     batch=self.shapes.train_batch,
                                     seq=self.shapes.train_seq)

    def _prefill_batch(self, i: int):
        batch = self.model.make_batch(jax.random.PRNGKey(200 + i),
                                      batch=self.shapes.prefill_batch,
                                      seq=self.shapes.prefill_seq,
                                      mode="prefill")
        batch.pop("labels", None)
        return batch

    def _cache(self):
        return init_params(self.model.cache_specs(self.shapes.decode_batch,
                                                  self.shapes.cache_len),
                           jax.random.PRNGKey(0))

    def simulation(self, i: int):
        """Decode rollout: ``decode_steps`` tokens per trajectory."""
        s = self.shapes
        cache = self._cache()
        tok = jnp.full((s.decode_batch, 1), 3, jnp.int32)
        finite = jnp.bool_(True)
        for t in range(s.decode_steps):
            pos = jnp.full((s.decode_batch,), t, jnp.int32)
            with self._lock:
                nxt, logits, cache = self.compiled["decode"](
                    self.state.params, cache, tok, pos)
            finite &= jnp.isfinite(logits).all()
            tok = nxt[:, None]
        self.logits_finite.append(finite)
        return jax.block_until_ready(tok)

    def aggregation(self, i: int):
        x = jax.random.normal(jax.random.PRNGKey(i), (1 << 16,))
        return jax.block_until_ready(jnp.sort(x)[::64].sum())

    def training(self, i: int):
        batch = self._train_batch(i)
        with self._lock:
            self.state, metrics = self.compiled["train"](self.state, batch)
        self.losses.append(metrics["loss"])
        return jax.block_until_ready(metrics["loss"])

    def inference(self, i: int):
        batch = self._prefill_batch(i)
        with self._lock:
            logits = self.compiled["prefill"](self.state.params, batch)
        self.logits_finite.append(jnp.isfinite(logits).all())
        return jax.block_until_ready(logits)

    def as_dict(self) -> dict:
        return dict(simulation=self.simulation, aggregation=self.aggregation,
                    training=self.training, inference=self.inference)


def dag(payloads: DDMDPayloads, iterations: int = 3):
    """Fig. 3a's staggered iterations at ``TABLE``'s task counts."""
    return deepdrivemd_dag(iterations, table=TABLE,
                           payloads=payloads.as_dict())


def run(payloads: DDMDPayloads, mode: str,
        iterations: int = 3) -> tuple[ExecResult, int]:
    """One DeepDriveMD run through ``RealExecutor`` on ``POOL``; returns
    the result and the DAG's task count.  Sequential mode runs one stage
    at a time."""
    g = dag(payloads, iterations)
    config = RunConfig(sequential_stage_groups=(
        ddmd_sequential_stage_groups(iterations)
        if mode == "sequential" else None))
    res = RealExecutor(POOL, launch_latency=0.002).run(g, mode,
                                                       config=config)
    return res, sum(ts.num_tasks for ts in g.nodes.values())
