"""The window: whole workflow instances through ``RealExecutor``.

One ``Workload`` per run.  It builds the program's ``DDMDPayloads`` once,
installs a train state made from the seed, and wraps, from outside the
program, the payload callables and the compiled steps they call, so that
it can see what each call read and produced:

- each payload call runs under ``TraceAnnotation("payload:<kind>")`` and
  leaves a host span and its task's identity;
- the compiled train step counts the state versions (train steps) that
  later calls read, and keeps its loss;
- for the calls the check samples, the compiled prefill and decode steps
  keep their outputs and inputs.

Each instance starts with the payloads' counters cleared and reports
them; with ``perf`` the executor counts its own (``PerfCounters``) too.

A traffic file (``bench/traffic/<name>.json``) gives the DAG: task sets
per iteration with their payload, task count and (cpus, gpus), edges with
``{i}`` and ``{i+1}`` placeholders, the pool, and the payload shapes.
Instances run back to back until the window closes; one still running
then is finished but not counted.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp

from bench import weights


@dataclasses.dataclass
class Span:
    """One payload call, as the harness saw it (host clock)."""
    inst: int
    set: str
    i: int
    kind: str
    index: int
    start: float
    end: float


@dataclasses.dataclass
class Record:
    """What the check needs of one instance."""
    inst: int
    #: training calls in the order they ran: (index, tokens, labels, loss,
    #: global gradient norm before clipping)
    train: list = dataclasses.field(default_factory=list)
    #: sampled prefill calls: (version, index, tokens, logits)
    prefill: list = dataclasses.field(default_factory=list)
    #: sampled rollouts: {(set, i): [(version, tok, pos, next, logits)]}
    decode: dict = dataclasses.field(default_factory=dict)
    #: per-leaf norms of the first step's first moment (warm-up only)
    mu_norms: object = None
    #: per-leaf norms of the parameters' change over the instance
    change_norms: object = None


def instance_dag(traffic: dict, payload: Callable[[str, str, int], Callable]):
    """The DAG of one instance: ``payload(kind, set_name, ordinal)``
    returns the task callable for a task set."""
    from repro.core.dag import DAG, TaskSet

    g = DAG()
    n_it = traffic["iterations"]
    for it in range(n_it):
        for k, s in enumerate(traffic["sets"]):
            name = f"{s['name']}{it}"
            g.add(TaskSet(name=name, num_tasks=s["tasks"],
                          cpus_per_task=s["cpus"], gpus_per_task=s["gpus"],
                          tx_mean=0.0, kind=s["payload"],
                          payload=payload(s["payload"], name,
                                          it * len(traffic["sets"]) + k)))
    for it in range(n_it):
        for u, v in traffic["edges"]:
            if "{i+1}" in u + v and it + 1 >= n_it:
                continue
            g.add_edge(u.replace("{i+1}", str(it + 1)).replace("{i}", str(it)),
                       v.replace("{i+1}", str(it + 1)).replace("{i}", str(it)))
    return g


def tasks_per_instance(traffic: dict) -> int:
    return traffic["iterations"] * sum(s["tasks"] for s in traffic["sets"])


def window_metrics(instances: list[tuple[float, float, int]],
                   deadline: float) -> dict:
    """From ``(start, end, tasks)`` of every instance run in the window:
    the whole instances (those that ended by ``deadline``), their count,
    summed wall and tasks.  An instance cut by the deadline is left out."""
    whole = [(s, e, n) for s, e, n in instances if e <= deadline]
    wall = sum(e - s for s, e, _ in whole)
    tasks = sum(n for _, _, n in whole)
    out = dict(instances=len(whole), wall_s=wall, tasks=tasks)
    if whole:
        out["makespan_s"] = wall / len(whole)
    return out


def task_base(seed: int) -> int:
    """Where this seed's task indices start (a multiplicative hash)."""
    return (seed * 2654435761) % weights.INDEX_SPAN


class Workload:
    """The program under test, driven as one cell's traffic says."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, model):
        from repro.launch import ddmd
        from repro.core.resources import NodeSpec, PoolSpec

        self.cfg, self.traffic = cfg, traffic
        self.per_inst = tasks_per_instance(traffic)
        pool = traffic["pool"]
        self.pool = PoolSpec("host", num_nodes=pool["nodes"],
                             node=NodeSpec(cpus=pool["cpus"],
                                           gpus=pool["gpus"]))
        self.payloads = ddmd.DDMDPayloads(
            model, ddmd.PayloadShapes(**traffic["shapes"]))
        # the program made a state from its own fixed key; the seed's
        # replaces it (the old one is freed first: both would not fit)
        self.payloads.state = None
        self._fresh = jax.jit(self._state_from_key)
        self._tls = threading.local()
        self._mu = threading.Lock()
        self._leaf_norms = jax.jit(
            lambda t: jnp.stack([jnp.linalg.norm(x.ravel())
                                 for x in jax.tree.leaves(t)]))
        self._change = jax.jit(
            lambda p, k: self._leaf_norms(jax.tree.map(
                jnp.subtract, p, weights.params_fn(cfg)(k))))
        self.record: Record | None = None
        self.sampled: set = set()
        self.warm_up = False
        #: the compiled steps as the program made them
        self.steps = dict(self.payloads.compiled)
        self._wrap_compiled()
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Start over from ``seed``: its state, task indices and samples."""
        self.seed = seed
        self.rng = random.Random(seed)
        self.base = task_base(seed)
        self._key = weights.seed_key(seed)
        self.spans: list[Span] = []
        #: (instance, DAG) of every instance run, for the schedule check
        self.runs: list = []
        self.reset()

    # -- state ---------------------------------------------------------------
    def _state_from_key(self, key):
        from repro.optim import AdamWState
        from repro.runtime.steps import TrainState

        params = weights.params_fn(self.cfg)(key)
        zeros = jax.tree.map(jnp.zeros_like, params)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt=AdamWState(step=jnp.zeros((), jnp.int32),
                                         mu=zeros,
                                         nu=jax.tree.map(jnp.zeros_like,
                                                         params)))

    def reset(self) -> None:
        """A fresh train state from the seed (each instance is one
        DeepDriveMD run from the same model)."""
        self.payloads.state = None
        self.payloads.state = jax.block_until_ready(self._fresh(self._key))
        self.version = 0

    def hlo_texts(self) -> list[str]:
        """The compiled HLO text of every program the payloads run."""
        return [c.as_text() for c in (*self.steps.values(),
                                      self.payloads.cast)]

    # -- wrappers ------------------------------------------------------------
    def _ctx(self):
        return getattr(self._tls, "ctx", None)

    def _wrap_compiled(self) -> None:
        c = self.payloads.compiled
        train, prefill, decode = c["train"], c["prefill"], c["decode"]

        def train_call(state, batch):
            new, metrics = train(state, batch)
            ctx = self._ctx()
            rec = self.record
            if rec is not None and ctx is not None:
                rec.train.append((ctx[3], batch["tokens"], batch["labels"],
                                  metrics["loss"], metrics["grad_norm"]))
                if self.warm_up and rec.mu_norms is None:
                    rec.mu_norms = self._leaf_norms(new.opt.mu)
            self.version += 1
            return new, metrics

        def prefill_call(params, batch):
            logits = prefill(params, batch)
            ctx = self._ctx()
            if ctx is not None and ctx[:3] in self.sampled:
                self.record.prefill.append((self.version, ctx[3],
                                            batch["tokens"], logits))
            return logits

        def decode_call(params, cache, tok, pos):
            nxt, logits, cache = decode(params, cache, tok, pos)
            ctx = self._ctx()
            if ctx is not None and ctx[:3] in self.sampled:
                steps = self.record.decode.setdefault(ctx[1:3], [])
                keep = len(steps) % self.traffic["check"]["logits_every"] == 0
                steps.append((self.version, tok, pos, nxt,
                              logits if keep else None))
            return nxt, logits, cache

        c["train"], c["prefill"], c["decode"] = (train_call, prefill_call,
                                                 decode_call)

    def task(self, inst: int, kind: str, set_name: str, ordinal: int):
        fn = getattr(self.payloads, kind)
        first = self.base + inst * self.per_inst + sum(
            s["tasks"] for s in self._sets_before(ordinal))
        label = f"payload:{kind}"

        def call(i: int):
            index = first + i
            self._tls.ctx = (inst, set_name, i, index)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                out = fn(index)
            t1 = time.perf_counter()
            self._tls.ctx = None
            with self._mu:
                self.spans.append(Span(inst, set_name, i, kind, index, t0,
                                       t1))
            return out

        return call

    def _sets_before(self, ordinal: int) -> list:
        sets = self.traffic["sets"] * self.traffic["iterations"]
        return sets[:ordinal]

    # -- one instance --------------------------------------------------------
    def _sample(self, inst: int, g) -> set:
        """Which calls of this instance the check keeps, drawn from the
        seed: rollouts and prefills."""
        chk = self.traffic["check"]
        out = set()
        for kind, n in (("simulation", chk["rollouts"]),
                        ("inference", chk["prefills"])):
            tasks = [(inst, ts.name, i) for ts in g.nodes.values()
                     if ts.kind == kind for i in range(ts.num_tasks)]
            out.update(self.rng.sample(tasks, min(n, len(tasks))))
        return out

    def run_instance(self, inst: int, perf: bool = False):
        """One whole instance; returns (start, end, Record, counters):
        ``payload``, the payloads' counters, and with ``perf`` also
        ``perf``, the executor's, as dicts."""
        from repro.core import RealExecutor, RunConfig

        g = instance_dag(self.traffic,
                         lambda kind, name, k: self.task(inst, kind, name, k))
        self.sampled = self._sample(inst, g)
        self.record = Record(inst)
        # the program's own per-call lists, which the check does not read
        self.payloads.losses.clear()
        self.payloads.logits_finite.clear()
        self.payloads.counters = type(self.payloads.counters)()
        t0 = time.perf_counter()
        res = RealExecutor(self.pool, launch_latency=0.0).run(
            g, "async", config=RunConfig(perf_counters=perf))
        t1 = time.perf_counter()
        counters = dict(payload=dataclasses.asdict(self.payloads.counters))
        if perf:
            counters["perf"] = dataclasses.asdict(res.perf)
        rec = self.record
        if self.warm_up:
            rec.change_norms = self._change(self.payloads.state.params,
                                            self._key)
        self.record = None
        self.runs.append((inst, g))
        return t0, t1, rec, counters

    def warm(self) -> Record:
        """The set-up's whole instance: it warms the executor and every
        small program the payloads dispatch, and the training check
        follows its train steps."""
        self.warm_up = True
        try:
            return self.run_instance(0)[2]
        finally:
            self.warm_up = False

    def window(self, seconds: float, trace_dir: str | None = None,
               profile_options=None) -> dict:
        """Whole instances back to back for ``seconds``; with
        ``trace_dir`` the profiler records the first one, under the
        ``bench:window`` annotation, and every instance counts the
        executor's ``PerfCounters``.  Returns the window's arithmetic,
        each instance's counters, and the record the check keeps: one
        counted instance, drawn from the seed by a reservoir of one."""
        from bench.trace import WINDOW

        pick = random.Random(self.seed ^ 0x5EED)
        kept, counted, spans, counters = None, 0, [], []
        perf = trace_dir is not None
        deadline = time.perf_counter() + seconds
        inst = 1
        while time.perf_counter() < deadline:
            self.reset()
            if inst == 1 and trace_dir:
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=profile_options)
                with jax.profiler.TraceAnnotation(WINDOW):
                    s, e, rec, c = self.run_instance(inst, perf)
                jax.profiler.stop_trace()
            else:
                s, e, rec, c = self.run_instance(inst, perf)
            spans.append((s, e, self.per_inst))
            counters.append(c)
            if e <= deadline:
                counted += 1
                if pick.random() < 1.0 / counted:
                    kept = rec
            inst += 1
        return dict(metrics=window_metrics(spans, deadline), kept=kept,
                    attempted=len(spans) * self.per_inst,
                    walls=[e - s for s, e, _ in spans], counters=counters)
