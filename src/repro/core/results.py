"""Shared run-result protocol of both execution substrates.

``SimResult`` (``core/simulator.py``) and ``ExecResult``
(``core/executor.py``) grew the same surface seven PRs in a row —
records, predictions, per-workflow stats, fault/admission counters —
duplicated field by field.  :class:`RunResult` is the extracted base
both now subclass, so benchmarks and tests consume one protocol instead
of special-casing the substrate, and the streaming-tenancy metrics (SLO
attainment, weighted-slowdown percentiles, sliding-window steady-state
stats) are defined exactly once.

:class:`TaskRecord` lives here too (it is the execution trace both
substrates emit); ``core/simulator.py`` re-exports it for existing
imports.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Sequence

from .workflow import WorkflowStats, weighted_slowdown

__all__ = ["TaskRecord", "RunResult", "PerfCounters",
           "per_pool_task_counts"]


def per_pool_task_counts(records: "Sequence[TaskRecord]") -> dict[str, int]:
    """How many tasks each pool of the allocation executed."""
    out: dict[str, int] = {}
    for r in records:
        out[r.pool] = out.get(r.pool, 0) + 1
    return out


@dataclasses.dataclass(frozen=True)
class TaskRecord:
    set_name: str
    index: int
    start: float
    end: float
    cpus: int
    gpus: int
    duplicate: bool = False
    #: name of the pool the task was placed on ("" for legacy records)
    pool: str = ""
    #: True when the task was preempted + migrated off a straggling pool
    #: (``pool`` is the pool it finally completed on)
    migrated: bool = False
    #: node index within the pool the winning attempt ran on (-1 on
    #: aggregate pools — see ``PoolSpec.node_level``)
    node: int = -1
    #: owning workflow of a campaign run ("" for single-workflow runs)
    workflow: str = ""

    @property
    def duration(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class PerfCounters:
    """Wall-time attribution of one run's hot loop
    (``RunConfig.perf_counters=True``), filled by both substrates; a
    field a substrate does not fill stays zero.

    Simulator: the buckets partition its event loop: ``engine_s`` is
    dispatch passes (``try_start`` + elastic/watchdog scans),
    ``predict_s`` is ``SchedEngine.repredict``, ``metrics_s`` is
    streaming-summary folding, and ``events_s`` is the remaining loop
    wall time (heap pops, event bookkeeping).  ``predicts`` counts
    *evaluated* predictions — throttled/deduped ``repredict`` calls that
    returned a cached prediction are excluded, which is how benchmarks
    attribute the prediction-epoch win.

    ``RealExecutor``: ``engine_s``/``passes`` are its dispatcher's
    backfill passes (arrivals, faults, ``startable``, submits),
    ``predict_s``/``predicts`` as above, ``total_s`` the dispatcher
    loop's wall; and the fields below, which the simulator leaves at
    zero: ``wait_s`` is the dispatcher's time in its condition wait and
    ``wait_timeouts`` the waits that ran out their timeout instead of
    being woken; ``starts`` counts task attempts a worker began, and
    ``handoff_s``/``handoff_max_s`` sum and bound the time from an
    attempt's submit to its worker's first line."""

    engine_s: float = 0.0
    predict_s: float = 0.0
    events_s: float = 0.0
    metrics_s: float = 0.0
    total_s: float = 0.0
    passes: int = 0
    predicts: int = 0
    events: int = 0
    wait_s: float = 0.0
    wait_timeouts: int = 0
    handoff_s: float = 0.0
    handoff_max_s: float = 0.0
    starts: int = 0


@dataclasses.dataclass
class RunResult:
    """What one run produced, whichever substrate executed it.

    Every field a substrate does not fill keeps its default — e.g. a
    single-workflow simulator run has ``workflows=None`` and all
    fault/lease counters at zero.  Both substrates construct their
    results keyword-only, so subclass field ordering is not part of the
    protocol."""

    makespan: float
    records: "list[TaskRecord]"
    mode: str = "async"
    tasks_total: int = 0
    #: scheduling policy used (see sched_engine.SCHEDULING_POLICIES)
    policy: str = "fifo"
    #: straggler preemption + migration count (runtime feedback enabled)
    migrations: int = 0
    #: speculative-duplicate launches (first finisher wins, loser freed)
    speculations: int = 0
    #: mid-run makespan re-predictions (``SchedEngine.repredict`` trace,
    #: feedback enabled; see ``core/predictor.py``)
    predictions: list = dataclasses.field(default_factory=list)
    #: per-workflow metrics of a campaign/stream run (None otherwise);
    #: see ``core/workflow.WorkflowStats``
    workflows: "dict[str, WorkflowStats] | None" = None
    #: task sets the admission controller deferred at least once
    admission_deferrals: int = 0
    #: workflows preemptively un-admitted for a deadline admit
    admission_revocations: int = 0
    #: fault injection (``faults=FaultOptions(...)``): applied node losses,
    #: software task failures, and the recovery arms taken per failure
    node_failures: int = 0
    task_failures: int = 0
    recoveries_restart: int = 0
    recoveries_rerun: int = 0
    #: proactive at-risk replications launched (``FaultOptions.replicate``)
    replications: int = 0
    #: the engine's failure trace: (time, kind, detail...) tuples
    fault_log: list = dataclasses.field(default_factory=list)
    #: elastic capacity (``RunConfig.elastic``): leases granted / expired
    #: and the (time, event, node) lease trace
    leases_granted: int = 0
    leases_expired: int = 0
    lease_log: list = dataclasses.field(default_factory=list)
    #: open-stream conservation partition (``stream_accounting``; None
    #: for closed campaigns / single workflows)
    stream: "dict | None" = None
    #: bounded streaming-summary accumulators
    #: (``RunConfig.record_policy="summary"``; ``core/metrics.py``).
    #: When set, ``records``/``workflows`` are empty and the metric
    #: surface below answers from the sketches instead.
    metrics: "object | None" = None
    #: hot-loop wall-time attribution (``RunConfig.perf_counters=True``)
    perf: "PerfCounters | None" = None

    # -- shared metric surface ---------------------------------------------
    def throughput(self) -> float:
        return self.tasks_total / self.makespan if self.makespan else 0.0

    def weighted_slowdown(self) -> "float | None":
        """Fairness-weighted mean slowdown of a campaign run (None for
        single-workflow runs or when no reference makespans are set)."""
        if not self.workflows:
            if self.metrics is not None:
                return self.metrics.weighted_slowdown()
            return None
        return weighted_slowdown(self.workflows)

    def workflow_records(self, name: str) -> "list[TaskRecord]":
        """The trace of one campaign workflow's tasks."""
        return [r for r in self.records if r.workflow == name]

    def per_pool_task_counts(self) -> dict[str, int]:
        return per_pool_task_counts(self.records)

    # -- streaming / SLO metrics -------------------------------------------
    # Repeated queries are the common shape (bench_check walks every
    # percentile of every baseline), so the sorted slowdown view and the
    # window buckets are memoized lazily on the instance; the memos
    # assume ``workflows`` is not mutated after the first query, which
    # both substrates guarantee (results are built once, at the end).
    def _slowdown_view(self):
        view = self.__dict__.get("_slow_view")
        if view is None:
            pts = sorted((w.slowdown, w.weight)
                         for w in (self.workflows or {}).values()
                         if w.slowdown is not None and w.weight > 0)
            cum: list[float] = []
            acc = 0.0
            for _s, wt in pts:
                acc += wt
                cum.append(acc)
            view = self.__dict__["_slow_view"] = (pts, cum)
        return view

    def slo_attainment(self) -> "float | None":
        """Fraction of deadline-carrying workflows that finished by their
        deadline (None when no workflow carries one)."""
        if not self.workflows:
            if self.metrics is not None:
                return self.metrics.slo_attainment()
            return None
        ws = [w for w in self.workflows.values()
              if w.deadline is not None]
        if not ws:
            return None
        return sum(1 for w in ws if w.met_deadline) / len(ws)

    def slowdown_percentile(self, q: float) -> "float | None":
        """Weight-respecting percentile of the per-workflow slowdowns
        (``q`` in [0, 1]; e.g. 0.99 for the P99 tail): the smallest
        slowdown at which the cumulative ``WorkflowEntry.weight`` mass
        reaches ``q``.  None when no workflow carries a
        ``reference_makespan``."""
        if not self.workflows and self.metrics is not None:
            return self.metrics.slowdown_percentile(q)
        pts, cum = self._slowdown_view()
        if not pts:
            return None
        # bisect over the cumulative mass == the linear acc-walk this
        # replaced (first point with acc >= q*total - 1e-12), minus the
        # per-call re-sort and re-scan
        idx = bisect.bisect_left(cum, q * cum[-1] - 1e-12)
        if idx >= len(pts):
            return pts[-1][0]
        return pts[idx][0]

    def window_stats(self, window: float) -> "list[dict]":
        """Steady-state view: workflows bucketed by *finish* time into
        consecutive windows of ``window`` modelled seconds; per window the
        finished count, SLO attainment and P50/P99 weighted slowdown (the
        streaming replacement for one end-of-run makespan).  Empty
        windows are omitted.  Summary-mode results
        (``record_policy="summary"``) answer from their fixed-width
        accumulators and reject any other ``window``."""
        if window <= 0:
            raise ValueError("window must be > 0")
        if not self.workflows and self.metrics is not None:
            if window != self.metrics.window:
                raise ValueError(
                    f"summary-mode run accumulated window={self.metrics.window}"
                    f" buckets; cannot re-bucket to window={window}")
            return self.metrics.window_stats()
        memo = self.__dict__.setdefault("_window_memo", {})
        out = memo.get(window)
        if out is not None:
            return out
        buckets: dict[int, list[WorkflowStats]] = {}
        for w in (self.workflows or {}).values():
            if w.tasks <= 0:
                continue  # never started (e.g. still deferred at the end)
            buckets.setdefault(int(w.finish // window), []).append(w)
        out = []
        for b in sorted(buckets):
            ws = buckets[b]
            sub = RunResult(makespan=0.0, records=[],
                            workflows={w.name: w for w in ws})
            out.append(dict(
                t0=b * window, t1=(b + 1) * window, finished=len(ws),
                slo_attainment=sub.slo_attainment(),
                p50_slowdown=sub.slowdown_percentile(0.50),
                p99_slowdown=sub.slowdown_percentile(0.99)))
        memo[window] = out
        return out
