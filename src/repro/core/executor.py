"""A real asynchronous executor for heterogeneous tasks.

This is the in-process analogue of the paper's EnTK + RADICAL-Pilot stack:
a *pilot* holds the allocation (CPU cores + accelerators), worker threads
execute black-box task payloads, and a dispatcher starts every
dependency-resolved task that fits in the free resources (backfilling).

Task payloads are arbitrary callables — in this framework they are
typically jitted JAX computations (training / inference steps), which
release the GIL while XLA executes, so heterogeneous tasks genuinely
overlap.  Synthetic tasks (``payload=None``) sleep for their sampled TX —
the `stress` analogue used by the paper's experiments.

All scheduling decisions — ready-queue order, dependency bookkeeping
(set-level by default, task-level with ``task_level=True``), per-pool
resource accounting and placement — are delegated to the SAME
:class:`~repro.core.sched_engine.SchedEngine` the discrete-event simulator
uses, so the two substrates enforce identical semantics by construction.
Heterogeneous multi-pool :class:`~repro.core.resources.Allocation`s and
the ``fifo`` / ``lpt`` / ``gpu_bestfit`` / ``locality`` / ``nodepack``
policies work unchanged here — node-level pools
(``PoolSpec.node_level``) stamp the concrete node of every winning
attempt onto its ``TaskRecord`` exactly as the simulator does — as does
runtime feedback (``feedback=FeedbackOptions()``):
completions feed the shared engine's online TX estimator (pool-tagged,
so per-pool splits work), a watchdog in the dispatcher mitigates
stragglers through the engine's arbiter — preempt + resubmit on another
pool (the abandoned attempt is invalidated by generation, exactly like
the simulator's migration events) or race a speculative duplicate
(first finisher wins; the loser is cancelled via the engine's finished
set) — and every scheduling pass re-predicts the makespan
(``ExecResult.predictions``, see ``core/predictor.py``).

Multi-workflow tenancy works here too: ``run()`` accepts a
:class:`~repro.core.workflow.Campaign` (arrivals gate dispatch on the
MODELLED clock — wall / ``tx_scale`` — so campaigns behave identically
to the simulator's), reports per-workflow metrics in
``ExecResult.workflows``, and honours ``admission=AdmissionOptions(...)``
through the shared engine.
"""

from __future__ import annotations

import dataclasses
import heapq
import math
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Sequence

from .dag import DAG
from .estimator import FeedbackOptions  # noqa: F401 (re-export surface)
from .resources import Allocation, PoolSpec
from .results import PerfCounters, RunResult, TaskRecord
from .runconfig import _LEGACY, RunConfig, resolve_run_config
from .sched_engine import AdmissionOptions, SchedEngine, SchedulingPolicy
from .simulator import Mode
from .stream import WorkflowStream, prefix_view
from .tracing import span
from .workflow import Campaign, CampaignView, campaign_stats
from ..runtime.fault import FailureSchedule, FaultOptions


@dataclasses.dataclass
class ExecResult(RunResult):
    """A real-executor run's result: exactly the shared
    :class:`~repro.core.results.RunResult` protocol.  ``records`` are in
    WALL seconds; ``workflows`` (and everything derived from it — SLO
    attainment, slowdown percentiles, window stats) is on the MODELLED
    clock (wall / ``tx_scale``), commensurate with the simulator's."""


class PayloadError(RuntimeError):
    """A task payload raised: the run stops at the first such exception,
    which is chained as ``__cause__``; ``task`` is its ``(set, index)``."""

    def __init__(self, name: str, i: int, err: BaseException):
        super().__init__(f"payload of task {name}[{i}] raised "
                         f"{type(err).__name__}: {err}")
        self.task = (name, i)


class RealExecutor:
    """Executes a task-set DG with real concurrency on the local host."""

    def __init__(self, pool: "PoolSpec | Allocation", max_workers: int = 64,
                 tx_scale: float = 1.0, seed: int = 0,
                 launch_latency: float = 0.0,
                 straggler_prob: float = 0.0,
                 straggler_factor: float = 4.0):
        self.pool = pool
        self.max_workers = max_workers
        #: wall-seconds per modelled TX second for synthetic payloads
        #: (lets laptop-scale runs validate Summit-scale workflows).
        self.tx_scale = tx_scale
        self.seed = seed
        self.launch_latency = launch_latency
        #: straggler injection for synthetic payloads (mirrors SimOptions):
        #: with probability p a task's sampled TX is stretched xfactor.
        self.straggler_prob = straggler_prob
        self.straggler_factor = straggler_factor

    def run(self, dag: "DAG | Campaign | WorkflowStream",
            mode: Mode = "async", *,
            config: "RunConfig | None" = None,
            task_level=_LEGACY,
            sequential_stage_groups=_LEGACY,
            scheduling=_LEGACY,
            feedback=_LEGACY,
            admission=_LEGACY,
            faults=_LEGACY,
            ) -> ExecResult:
        """Execute ``dag`` (a DAG, a closed Campaign, or an open
        :class:`~repro.core.stream.WorkflowStream` consumed incrementally
        on the modelled clock).  Scheduling-semantics knobs arrive in
        ``config=RunConfig(...)``; the individual keyword arguments are
        the deprecated legacy form (bit-identical, not mixable with
        ``config=`` — see ``core/runconfig.py``)."""
        cfg = resolve_run_config(config, dict(
            task_level=task_level,
            sequential_stage_groups=sequential_stage_groups,
            scheduling=scheduling, feedback=feedback,
            admission=admission, faults=faults), "RealExecutor.run()")
        task_level = cfg.task_level
        sequential_stage_groups = cfg.sequential_stage_groups
        scheduling = cfg.scheduling
        feedback = cfg.feedback
        admission = cfg.admission
        faults = cfg.faults
        perf = PerfCounters() if cfg.perf_counters else None
        if cfg.record_policy != "full":
            # the executor's records ARE its measurement (wall-clock
            # spans); only the simulator can trade them for sketches
            raise ValueError(
                f"record_policy={cfg.record_policy!r} is simulator-only "
                f"(RealExecutor always keeps the full trace)")

        stream: "WorkflowStream | None" = None
        if isinstance(dag, WorkflowStream):
            closed = dag.closed_campaign
            if closed is not None:
                dag = closed  # a closed stream IS its campaign
            else:
                stream = dag
                stream.reset()
        view: "CampaignView | None" = None
        arrived_entries: "list" = []
        if stream is not None:
            if mode != "async":
                raise ValueError("streams execute asynchronously "
                                 "(mode='async')")
            arrived_entries = list(stream.take_until(0.0))
            view = prefix_view(arrived_entries, stream.name)
            g = view.dag
        elif isinstance(dag, Campaign):
            if mode != "async":
                raise ValueError("campaigns execute asynchronously "
                                 "(mode='async')")
            view = dag.view()
            g = view.dag
        else:
            g = dag if mode == "async" else dag.with_sequential_barriers(
                sequential_stage_groups)
        rng = random.Random(self.seed)
        engine = SchedEngine(g, self.pool, policy=scheduling,
                             task_level=task_level, feedback=feedback,
                             campaign=view, admission=admission,
                             faults=faults, elastic=cfg.elastic,
                             predict=cfg.predict,
                             incremental=cfg.incremental)
        # live for streams (add_workflow extends it); a superset-correct
        # copy of view.workflow_of for closed campaigns
        wf_of = engine.workflow_of if view is not None else {}
        #: distinct workflow arrivals (modelled s), for dispatcher wakeups
        arrivals = (sorted({w.arrival for w in view.entries})
                    if view is not None else [])
        faults = engine.faults  # disabled options normalized to None
        schedule = (FailureSchedule(faults,
                                    [(k, p.num_nodes)
                                     for k, p in enumerate(engine.pools)],
                                    [p.name for p in engine.pools])
                    if faults is not None else None)

        durations: dict[tuple[str, int], float] = {}

        def sample_durations(names: "Sequence[str]") -> None:
            """Pre-sample every task of ``names`` in set order (the RNG
            draw order is part of the trace contract)."""
            for name in names:
                ts = g.node(name)
                for i in range(ts.num_tasks):
                    mu = ts.tx_mean
                    d = max(0.0, rng.gauss(mu, ts.tx_sigma)) if mu else 0.0
                    if (self.straggler_prob
                            and rng.random() < self.straggler_prob):
                        d *= self.straggler_factor
                    durations[(name, i)] = d

        sample_durations(engine.order)

        lock = threading.Lock()
        cv = threading.Condition(lock)
        records: list[TaskRecord] = []
        #: wall start of the task's CURRENT attempt, stamped when a worker
        #: actually begins it (NOT at submit — tasks queued behind
        #: max_workers must not accrue phantom straggler runtime) and
        #: absent between a preemption and its re-run's first breath
        started: dict[tuple[str, int], float] = {}
        #: wall start of the FIRST attempt (task records span the task)
        first_start: dict[tuple[str, int], float] = {}
        #: attempt generation; a migration bumps it, invalidating the
        #: preempted attempt's completion (same scheme as the simulator).
        #: Under faults a failure of the primary attempt bumps it too.
        gen: dict[tuple[str, int], int] = {}
        #: speculative-attempt generation: bumped to invalidate a racing
        #: duplicate whose node died (``FailureEvent.cancelled``) without
        #: touching the primary's ``gen``
        spec_gen: dict[tuple[str, int], int] = {}
        #: duplicates promoted to primary (their primary's node died):
        #: the spec worker completes the task as the primary instead
        promoted_keys: set[tuple[str, int]] = set()
        #: the first payload exception (set, index, exception): it ends
        #: the run and is re-raised from ``run()``
        failure: list[tuple[str, int, BaseException]] = []
        t0 = time.perf_counter()

        def preemptible_sleep(name: str, i: int, my_gen: int,
                              seconds: float, spec: bool = False) -> bool:
            """Sleep that wakes early when the attempt is preempted (gen
            bumped) or another attempt already finished the task, so an
            abandoned synthetic attempt does not hold its worker slot for
            the full straggler duration.  True = slept to completion,
            False = superseded.  (Real payloads cannot be interrupted this
            way — they run to completion and their stale result is
            discarded at the completion check.)  Speculative attempts
            check their own generation (``spec_gen``): a primary-side
            failure must not abort the replica racing to replace it."""
            deadline = time.perf_counter() + seconds
            g_of = spec_gen if spec else gen
            with cv:
                while True:
                    if (failure or my_gen != g_of.get((name, i), 0)
                            or (name, i) in engine.finished):
                        return False
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        return True
                    cv.wait(timeout=remaining)

        def apply_failure_event(ev) -> None:
            """Invalidate the worker attempts a FailureEvent superseded
            (caller holds ``cv``).  Failed primaries bump ``gen`` (their
            synthetic sleeps wake and abort; the engine already re-enqueued
            the task); a promoted replica's primary dies the same way but
            the replica keeps racing and will complete as the primary; a
            cancelled replica bumps ``spec_gen`` only."""
            for key in ev.failed:
                gen[key] = gen.get(key, 0) + 1
                spec_gen[key] = spec_gen.get(key, 0) + 1
                promoted_keys.discard(key)
                started.pop(key, None)
            for key in ev.promoted:
                gen[key] = gen.get(key, 0) + 1
                promoted_keys.add(key)
                started.pop(key, None)
            for key in ev.cancelled:
                spec_gen[key] = spec_gen.get(key, 0) + 1
            cv.notify_all()

        #: tasks that were straggler-migrated (the record flag; under
        #: faults ``gen`` is also bumped by failures)
        mig_tasks: set[tuple[str, int]] = set()

        def valid(name: str, i: int, my_gen: int, spec: bool) -> bool:
            """Is this attempt still the live one? (caller holds ``cv``)"""
            if failure or (name, i) in engine.finished:
                return False
            g_of = spec_gen if spec else gen
            return my_gen == g_of.get((name, i), 0)

        def body(name: str, i: int, pool_idx: int, my_gen: int,
                 migration_cost: float = 0.0,
                 rerun_tx: float = 0.0,
                 spec: bool = False,
                 fail_frac: "float | None" = None) -> None:
            ts = g.node(name)
            with cv:
                if not valid(name, i, my_gen, spec):
                    return  # superseded while still queued
                first_start.setdefault((name, i),
                                       time.perf_counter() - t0)
            if self.launch_latency:
                time.sleep(self.launch_latency)
            if migration_cost:
                # data movement for a migrated or speculative re-run
                time.sleep(migration_cost * self.tx_scale)
            with cv:
                if not valid(name, i, my_gen, spec):
                    return
                # straggler/estimator clock starts when the WORK starts:
                # raw launch latency and migration/data cost must not read
                # as (tx_scale-modelled) task duration.  A speculative
                # duplicate keeps its own clock — the original's straggler
                # clock must keep running while they race.
                work_start = time.perf_counter() - t0
                if not spec:
                    started[(name, i)] = work_start
            if ts.payload is not None:
                ts.payload(i)
            elif not spec and fail_frac is not None:
                # seeded software failure: the attempt dies at fail_frac
                # of its run and the engine re-enqueues (or promotes)
                if not preemptible_sleep(name, i, my_gen,
                                         fail_frac * rerun_tx
                                         * self.tx_scale):
                    return
                with cv:
                    if not valid(name, i, my_gen, spec=False):
                        return
                    nowm = (time.perf_counter() - t0) / self.tx_scale
                    ev = engine.fail_task(name, i, now=nowm,
                                          elapsed=fail_frac * rerun_tx)
                    if ev is not None:
                        apply_failure_event(ev)
                return
            elif spec or my_gen or faults is not None:
                # migrated or speculative re-run (regardless of the
                # fabric's cost): a fresh attempt at the TX estimate read
                # at mitigation time.  Under faults every dispatch passes
                # its recovery/checkpoint-adjusted duration this way.
                if not preemptible_sleep(name, i, my_gen,
                                         rerun_tx * self.tx_scale, spec):
                    return
            else:
                if not preemptible_sleep(name, i, my_gen,
                                         durations[(name, i)]
                                         * self.tx_scale):
                    return
            end = time.perf_counter() - t0
            with cv:
                if not valid(name, i, my_gen, spec):
                    return  # lost the race / preempted; not ours anymore
                won_promoted = spec and (name, i) in promoted_keys
                attempt_start = (work_start if spec
                                 else started.pop((name, i), end))
                if spec:
                    started.pop((name, i), None)
                start = first_start.pop((name, i), attempt_start)
                # node id must be read before complete() frees the slot
                if won_promoted:
                    # the replica became the primary when the original's
                    # node died: finish the task as the primary attempt
                    promoted_keys.discard((name, i))
                    node = engine.node_placement(name, i)
                    engine.complete(name, i)
                else:
                    node = (engine.spec_node(name, i) if spec
                            else engine.node_placement(name, i))
                    # a winning duplicate's placement becomes the task's
                    # final one (children's data costs price the actual
                    # output node)
                    engine.complete(name, i, spec_won=spec)
                # observe in MODELLED seconds (wall / tx_scale) so the
                # estimates stay commensurate with the tx_mean priors and
                # the allocation's transfer costs
                engine.observe(name, (end - attempt_start) / self.tx_scale,
                               pool=pool_idx)
                records.append(TaskRecord(name, i, start, end,
                                          ts.cpus_per_task, ts.gpus_per_task,
                                          duplicate=spec and not won_promoted,
                                          pool=engine.pool_name(pool_idx),
                                          migrated=(name, i) in mig_tasks,
                                          node=node,
                                          workflow=wf_of.get(name, "")))
                cv.notify_all()

        def attempt(submitted: float, name: str, i: int, *args) -> None:
            """Worker entry: ``body`` under an ``exec:task`` span, with its
            exception also handed to the dispatcher, which would otherwise
            wait for a completion that never comes.  ``submitted`` is the
            clock at ``ex.submit``: the hand-off runs from there to here."""
            handoff = time.perf_counter() - submitted
            if perf is not None:
                with cv:
                    perf.starts += 1
                    perf.handoff_s += handoff
                    perf.handoff_max_s = max(perf.handoff_max_s, handoff)
            try:
                with span("exec:task", task=f"{name}[{i}]",
                          handoff_us=handoff * 1e6):
                    body(name, i, *args)
            except BaseException as err:
                with cv:
                    failure.append((name, i, err))
                    cv.notify_all()
                raise

        # the watchdog needs a mitigation that can actually fire: migration
        # needs a second pool; speculation only needs a free slot, so it
        # keeps the watchdog alive even on single-pool allocations.
        # Proactive replication rides the same cadence.
        watchdog = (feedback is not None
                    and (feedback.speculate
                         or (feedback.migrate and len(engine.pools) > 1)))
        replicating = faults is not None and faults.replicate
        #: next node-failure event from the shared schedule (modelled s)
        next_fail = (schedule.next_node_failure()
                     if schedule is not None else None)
        #: pending node recoveries: (modelled time, pool, node) heap
        recoveries: list[tuple[float, int, int]] = []
        #: next elastic control step (modelled s)
        next_elastic = (engine.elastic.check_interval
                        if engine.elastic is not None else math.inf)

        def stream_pending() -> bool:
            return stream is not None and stream.next_arrival() is not None

        def submit(*args) -> None:
            ex.submit(attempt, time.perf_counter(), *args)

        #: engine snapshot behind the newest prediction (idle-wakeup guard)
        last_stamp = None
        t_loop = time.perf_counter()
        with ThreadPoolExecutor(max_workers=self.max_workers) as ex:
            with cv:
                while (not engine.done() or stream_pending()) \
                        and not failure:
                    # backfill: start everything ready that fits.  The
                    # pass runs on the modelled clock (see observe) so
                    # campaign arrivals gate on the same time base as the
                    # simulator's — and so do failure/recovery, stream
                    # arrival, and elastic lease events
                    t_pass = time.perf_counter() if perf is not None else 0.0
                    with span("exec:pass") as pass_span:
                        now = (time.perf_counter() - t0) / self.tx_scale
                        if stream is not None:
                            new_names: list[str] = []
                            for w in stream.take_until(now):
                                arrived_entries.append(w)
                                new_names.extend(
                                    engine.add_workflow(w, now=now))
                            sample_durations(new_names)
                        if now >= next_elastic:
                            engine.elastic_pass(now)
                            next_elastic = (now
                                            + engine.elastic.check_interval)
                        while recoveries and recoveries[0][0] <= now:
                            _, rk, rn = heapq.heappop(recoveries)
                            engine.recover_node(rk, rn, now=now)
                        while (next_fail is not None
                               and next_fail[0] <= now
                               and not engine.done()):
                            _, fk, fn = next_fail
                            modelled = {k: v / self.tx_scale
                                        for k, v in started.items()}
                            ev = engine.fail_node(fk, fn, now=now,
                                                  started=modelled)
                            if ev is not None:
                                apply_failure_event(ev)
                                if math.isfinite(faults.node_recovery_time):
                                    heapq.heappush(
                                        recoveries,
                                        (now + faults.node_recovery_time,
                                         fk, fn))
                            next_fail = schedule.next_node_failure()
                        batch = engine.startable(now)
                        for name, i, pool_idx in batch:
                            if faults is None:
                                submit(name, i, pool_idx, 0)
                                continue
                            d = engine.dispatch_duration(
                                name, i, durations[(name, i)], pool_idx)
                            frac = schedule.attempt_failure(
                                name, i, engine.attempt_number(name, i))
                            submit(name, i, pool_idx, gen.get((name, i), 0),
                                   0.0, d, False, frac)
                        pass_span.set_metadata(started=len(batch))
                    if perf is not None:
                        perf.engine_s += time.perf_counter() - t_pass
                        perf.passes += 1
                    if (not engine.done() or stream_pending()) \
                            and not batch and not failure:
                        # with mitigation on, the wait doubles as the
                        # straggler watchdog cadence; a pending campaign
                        # arrival (or fault/recovery/stream/lease event)
                        # bounds the sleep so its pass is not missed
                        timeout = 0.05 if (watchdog or replicating) else 5.0
                        nxt = next((a for a in arrivals if a > now), None)
                        if next_fail is not None:
                            nxt = (next_fail[0] if nxt is None
                                   else min(nxt, next_fail[0]))
                        if recoveries:
                            nxt = (recoveries[0][0] if nxt is None
                                   else min(nxt, recoveries[0][0]))
                        if stream_pending():
                            na = stream.next_arrival()
                            nxt = na if nxt is None else min(nxt, na)
                        if next_elastic < math.inf:
                            nxt = (next_elastic if nxt is None
                                   else min(nxt, next_elastic))
                        if nxt is not None:
                            timeout = min(timeout, max(
                                0.0, (nxt - now) * self.tx_scale) + 1e-3)
                        t_wait = (time.perf_counter() if perf is not None
                                  else 0.0)
                        with span("exec:wait") as wait_span:
                            woken = cv.wait(timeout=timeout)
                            wait_span.set_metadata(timeout=int(not woken))
                        if perf is not None:
                            perf.wait_s += time.perf_counter() - t_wait
                            perf.wait_timeouts += not woken
                    # scheduling pass on the modelled clock (see observe)
                    now = (time.perf_counter() - t0) / self.tx_scale
                    modelled = {k: v / self.tx_scale
                                for k, v in started.items()}
                    if watchdog:
                        for (sn, si) in engine.stragglers(modelled, now):
                            act = engine.arbitrate(
                                sn, si, now - modelled[(sn, si)])
                            if act is None:
                                continue
                            kind, dst, cost = act
                            if kind == "migrate":
                                gen[(sn, si)] = gen.get((sn, si), 0) + 1
                                mig_tasks.add((sn, si))
                                # straggler clock pauses until the re-run's
                                # worker stamps its own start
                                started.pop((sn, si), None)
                                submit(sn, si, dst, gen[(sn, si)], cost,
                                       engine.tx_estimate(sn, pool=dst))
                                # wake preempted synthetic sleeps so they
                                # release their worker slots promptly
                                cv.notify_all()
                            else:  # speculate: a duplicate races the task
                                submit(sn, si, dst, spec_gen.get((sn, si), 0),
                                       cost, engine.tx_estimate(sn, pool=dst),
                                       True)
                    if replicating:
                        # proactively duplicate at-risk tasks onto another
                        # node through the speculation machinery
                        for (rn2, ri2) in engine.at_risk(modelled, now):
                            rep = engine.try_replicate(rn2, ri2)
                            if rep is None:
                                continue
                            dst, cost = rep
                            submit(rn2, ri2, dst, spec_gen.get((rn2, ri2), 0),
                                   cost, engine.tx_estimate(rn2, pool=dst),
                                   True)
                    # online makespan re-prediction (core/predictor.py).
                    # The dispatcher's poll loop wakes on a timeout even
                    # when nothing happened; an idle wakeup (no running
                    # tasks, no engine state moved since the last
                    # snapshot) would append one identical prediction per
                    # poll — skip those, re-predict on everything else
                    if (modelled or not engine.predictions
                            or engine.predict_stamp() != last_stamp):
                        t_pred = (time.perf_counter() if perf is not None
                                  else 0.0)
                        with span("exec:predict"):
                            engine.repredict(now, modelled)
                        if perf is not None:
                            perf.predict_s += time.perf_counter() - t_pred
                        last_stamp = engine.predict_stamp()
                if perf is not None:
                    perf.total_s = time.perf_counter() - t_loop
                    perf.predicts = engine._pred_evals
            if failure:
                # queued attempts never start; running ones see
                # ``failure`` at their next check and return
                ex.shutdown(wait=False, cancel_futures=True)
        if failure:
            name, i, err = failure[0]
            raise PayloadError(name, i, err) from err

        makespan = max((r.end for r in records), default=0.0)
        if stream is not None:
            # final per-workflow stats span everything that arrived (the
            # re-merged view names sets exactly as add_workflow did)
            view = prefix_view(arrived_entries, stream.name)
        workflows = None
        if view is not None:
            # per-workflow stats on the MODELLED clock, commensurate with
            # the entries' arrival times and the simulator's metrics
            scale = self.tx_scale or 1.0
            scaled = [dataclasses.replace(r, start=r.start / scale,
                                          end=r.end / scale)
                      for r in records]
            workflows = campaign_stats(view, scaled)
        return ExecResult(makespan=makespan, records=records,
                          mode=mode if not task_level else f"{mode}+task_level",
                          tasks_total=len(records),
                          policy=engine.policy.name,
                          migrations=engine.migrations,
                          speculations=engine.speculations,
                          predictions=engine.predictions,
                          workflows=workflows,
                          admission_deferrals=engine.admission_deferrals,
                          node_failures=engine.node_failures,
                          task_failures=engine.task_failures,
                          recoveries_restart=engine.recoveries_restart,
                          recoveries_rerun=engine.recoveries_rerun,
                          replications=engine.replications,
                          fault_log=engine.fault_log,
                          admission_revocations=engine.admission_revocations,
                          leases_granted=engine.leases_granted,
                          leases_expired=engine.leases_expired,
                          lease_log=engine.lease_log,
                          stream=(engine.stream_accounting()
                                  if stream is not None else None),
                          perf=perf)
