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

Rollouts and prefills read a serving copy of the params
(``Model.serving_params``): the weights the steps would cast to bfloat16
at every use, cast once.  One copy serves every decode and prefill step
on one train-state version; it is made under the lock by the first such
call after the state changes, and dropped when it does.  Without it each
decode step re-casts the whole float32 tree and writes the copies out.

Each payload call is a ``ddmd:<kind>`` span (``repro.core.tracing``); in
it, ``ddmd:lock`` spans the wait for the lock (not the dispatch under it)
and ``ddmd:block`` the wait for the device.  ``counters`` sums those two
waits, and an expert model's train steps' assignments to each expert.

``run`` drives the workflow through ``RealExecutor`` with the same
(cpus, gpus) accounting as the paper's middleware: sequential mode
barriers each stage, async mode staggers the iterations.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import RealExecutor, RunConfig, deepdrivemd_dag
from repro.core.executor import ExecResult
from repro.core.resources import NodeSpec, PoolSpec
from repro.core.tracing import span
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


@dataclasses.dataclass
class PayloadCounters:
    """Host seconds the payloads spent waiting: for the state's lock
    (per acquire) and in ``block_until_ready`` (per call)."""
    lock_acquires: int = 0
    lock_wait_s: float = 0.0
    lock_wait_max_s: float = 0.0
    block_s: float = 0.0
    block_max_s: float = 0.0
    #: serving copies made (one per train-state version that a rollout or
    #: prefill read)
    casts: int = 0
    #: an expert model's train steps: assignments to each held expert,
    #: summed over layers and steps
    expert_tokens: list = dataclasses.field(default_factory=list)


class DDMDPayloads:
    """The four payloads over one model, its steps compiled ahead of time
    at ``shapes`` (``compiled`` keeps them for memory and HLO checks).

    ``losses`` and ``logits_finite`` collect what each call produced, as
    device scalars, for the caller to check after a run; ``counters``
    sums the calls' waits (a caller clears it by replacing it).

    Prefill and decode run on the serving copy of ``state.params``, made
    by ``cast`` (compiled ahead of time too, as ``jit_serving_params``).
    ``state`` is a property so that assigning it, as a caller that
    reseeds the state does, drops the copy of the old state; the copy
    holds arrays of its own, so the old state is freed with it."""

    def __init__(self, model: Model, shapes: PayloadShapes = PayloadShapes()):
        self.model = model
        self.shapes = shapes
        self.state = make_train_state(model, jax.random.PRNGKey(0))
        self._lock = threading.Lock()
        #: guards the block counters (the lock's own are updated under it)
        self._count_lock = threading.Lock()
        self.counters = PayloadCounters()
        self.losses: list[jax.Array] = []
        self.logits_finite: list[jax.Array] = []
        s = shapes
        train, _ = build_train_step(model, opts=TrainOptions(total_steps=100))
        prefill, _ = build_prefill_step(model)
        decode, _ = build_decode_step(model, batch=s.decode_batch,
                                      s_max=s.cache_len)
        tok = jnp.zeros((s.decode_batch, 1), jnp.int32)
        pos = jnp.zeros((s.decode_batch,), jnp.int32)
        self.cast = jax.jit(model.serving_params).lower(
            self.state.params).compile()
        serving = self.cast.out_info
        self.compiled = dict(
            train=train.lower(self.state, self._train_batch(0)).compile(),
            prefill=prefill.lower(serving, self._prefill_batch(0)).compile(),
            decode=decode.lower(serving, self._cache(), tok, pos).compile(),
        )

    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, state) -> None:
        self._state = state
        self._serving = None

    def _serving_params(self):
        """The serving copy of the current params; call under the lock.
        With none for this state, dispatch the cast first: the device
        runs it after every step dispatched before it."""
        if self._serving is None:
            self._serving = self.cast(self.state.params)
            self.counters.casts += 1
        return self._serving

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

    @contextlib.contextmanager
    def _locked(self):
        """Hold the state's lock; the wait for it is a ``ddmd:lock`` span
        and is counted."""
        t0 = time.perf_counter()
        with span("ddmd:lock"):
            self._lock.acquire()
        try:
            wait = time.perf_counter() - t0
            c = self.counters
            c.lock_acquires += 1
            c.lock_wait_s += wait
            c.lock_wait_max_s = max(c.lock_wait_max_s, wait)
            yield
        finally:
            self._lock.release()

    def _block(self, x):
        """``block_until_ready`` as a ``ddmd:block`` span, counted."""
        t0 = time.perf_counter()
        with span("ddmd:block"):
            out = jax.block_until_ready(x)
        wait = time.perf_counter() - t0
        with self._count_lock:
            c = self.counters
            c.block_s += wait
            c.block_max_s = max(c.block_max_s, wait)
        return out

    def simulation(self, i: int):
        """Decode rollout: ``decode_steps`` tokens per trajectory."""
        with span("ddmd:simulation"):
            s = self.shapes
            cache = self._cache()
            tok = jnp.full((s.decode_batch, 1), 3, jnp.int32)
            finite = jnp.bool_(True)
            for t in range(s.decode_steps):
                pos = jnp.full((s.decode_batch,), t, jnp.int32)
                with self._locked():
                    nxt, logits, cache = self.compiled["decode"](
                        self._serving_params(), cache, tok, pos)
                finite &= jnp.isfinite(logits).all()
                tok = nxt[:, None]
            self.logits_finite.append(finite)
            return self._block(tok)

    def aggregation(self, i: int):
        with span("ddmd:aggregation"):
            x = jax.random.normal(jax.random.PRNGKey(i), (1 << 16,))
            return self._block(jnp.sort(x)[::64].sum())

    def training(self, i: int):
        with span("ddmd:training"):
            batch = self._train_batch(i)
            with self._locked():
                # the old copy is freed once the steps queued on it ran
                self._serving = None
                self.state, metrics = self.compiled["train"](self.state,
                                                             batch)
            self.losses.append(metrics["loss"])
            out = self._block(metrics["loss"])
            if "expert_tokens" in metrics:
                # the step has run: reading its counts waits for nothing
                counts = np.asarray(metrics["expert_tokens"]).tolist()
                with self._count_lock:
                    c = self.counters
                    c.expert_tokens = [a + b for a, b in zip(
                        c.expert_tokens or [0.0] * len(counts), counts)]
            return out

    def inference(self, i: int):
        with span("ddmd:inference"):
            batch = self._prefill_batch(i)
            with self._locked():
                logits = self.compiled["prefill"](self._serving_params(),
                                                  batch)
            self.logits_finite.append(jnp.isfinite(logits).all())
            return self._block(logits)

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
