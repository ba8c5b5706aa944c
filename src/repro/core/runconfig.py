"""Unified run configuration for both execution substrates.

Seven PRs of options accreted into parallel kwarg sprawl on
``simulate()`` and ``RealExecutor.run()`` (``scheduling=``,
``feedback=``, ``admission=``, ``faults=``, ...).  :class:`RunConfig`
bundles them — plus the streaming-tenancy knobs this PR adds
(``elastic``, ``slo_window``) — into one frozen dataclass accepted as
``simulate(dag, pool, config=RunConfig(...))`` and
``executor.run(dag, config=RunConfig(...))``.

Legacy kwargs keep working through :func:`resolve_run_config`: the shim
emits one :class:`DeprecationWarning` per process *per call site* (the
``where`` string — ``simulate()`` and ``RealExecutor.run()`` each warn
once) the first time that site sees a legacy kwarg, and *forbids
mixing* the kwarg and config forms in one call (silently preferring
either would make the other a no-op).  Resolution is purely mechanical
— a legacy call and its ``RunConfig`` equivalent produce bit-identical
runs.  Tests reset the warn-once state with
:func:`reset_legacy_warnings`.
"""

from __future__ import annotations

import dataclasses
import warnings

from ..runtime.fault import FaultOptions
from .estimator import FeedbackOptions
from .resources import ElasticOptions
from .sched_engine import AdmissionOptions, PredictOptions, SchedulingPolicy

__all__ = ["RunConfig", "resolve_run_config", "reset_legacy_warnings"]

#: sentinel distinguishing "kwarg not passed" from an explicit None/default
#: (passing ``scheduling="fifo"`` explicitly still counts as legacy usage)
_LEGACY = object()


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Everything about *how* to run a workload, substrate-independent.

    What to run (DAG / Campaign / WorkflowStream), where (PoolSpec /
    Allocation) and the substrate's own physics (SimOptions sampling,
    RealExecutor tx_scale) stay separate arguments — this bundles the
    scheduling-semantics knobs the two substrates must agree on."""

    #: scheduling policy name or instance (``SCHEDULING_POLICIES``)
    scheduling: "str | SchedulingPolicy" = "fifo"
    #: task-level dependency granularity (the paper's future-work mode)
    task_level: bool = False
    #: explicit PST stage groups for ``mode="sequential"``
    sequential_stage_groups: "list | None" = None
    #: runtime feedback / straggler mitigation (``core/estimator.py``)
    feedback: "FeedbackOptions | None" = None
    #: prediction-driven admission control (campaign/stream runs)
    admission: "AdmissionOptions | None" = None
    #: fault injection + priced recovery (``runtime/fault.py``)
    faults: "FaultOptions | None" = None
    #: elastic capacity leases (``core/resources.ElasticOptions``)
    elastic: "ElasticOptions | None" = None
    #: sliding-window width (modelled s) for ``RunResult.window_stats``
    #: consumers; recorded on the config for benchmarks to share
    slo_window: "float | None" = None
    #: prediction-epoch throttling of ``SchedEngine.repredict``
    #: (``PredictOptions``; None = re-evaluate on every scheduling pass).
    #: Placement-neutral by construction — predictions inform the trace
    #: and the mitigation arbiter's inputs are computed separately — so
    #: throttling thins the prediction *trace* without moving a task.
    predict: "PredictOptions | None" = None
    #: drain all same-timestamp heap events (arrival batches, completion
    #: bursts) into one scheduling pass + one repredict instead of N
    coalesce_events: bool = False
    #: "full" keeps the per-task ``TaskRecord`` trace and per-workflow
    #: stats dict; "summary" (simulator-only) streams finished workflows
    #: into bounded ``core/metrics.StreamMetrics`` sketches instead,
    #: capping memory on million-task runs
    record_policy: str = "full"
    #: collect ``RunResult.perf`` hot-loop wall-time attribution in the
    #: simulator and in ``RealExecutor`` (which also fills ``wait_s``,
    #: ``wait_timeouts``, ``handoff_s``, ``handoff_max_s``, ``starts``;
    #: see ``PerfCounters``) — pure-Python timers; zero overhead when False
    perf_counters: bool = False
    #: engine pass structures: the indexed fast path (default) vs the
    #: brute-force scans (``core/sched_engine.py``); dispatch-identical
    #: by the engine's invariant suite — exposed here so determinism
    #: tests (and A/B runs) can flip it through the public run API
    incremental: bool = True


#: call sites (``where`` strings) that have already warned this process.
#: Keyed per site — one module-level bool silenced every call site after
#: the first, so whichever entry point a test module happened to exercise
#: first stole the warning from the others (test order decided which
#: ``pytest.warns`` assertion saw it).
_warned_sites: "set[str]" = set()


def reset_legacy_warnings() -> None:
    """Forget which call sites have warned (test hook: lets a test assert
    the warn-once behaviour without depending on process history)."""
    _warned_sites.clear()


def _warn_legacy(where: str, names: "list[str]") -> None:
    if where in _warned_sites:
        return
    _warned_sites.add(where)
    warnings.warn(
        f"{where}: passing {', '.join(sorted(names))} as separate keyword "
        f"arguments is deprecated — bundle them in config=RunConfig(...) "
        f"(this warning is emitted once per call site per process)",
        DeprecationWarning, stacklevel=4)


def resolve_run_config(config: "RunConfig | None", legacy: dict,
                       where: str) -> RunConfig:
    """Fold a substrate entry point's arguments into one ``RunConfig``.

    ``legacy`` maps kwarg name -> passed value, with the module-level
    ``_LEGACY`` sentinel marking "not passed".  Mixing any legacy kwarg
    with ``config=`` raises ``TypeError``; pure-legacy calls warn once
    per call site (``where``) per process and resolve to the equivalent
    config."""
    used = {k: v for k, v in legacy.items() if v is not _LEGACY}
    if config is not None:
        if used:
            raise TypeError(
                f"{where}: pass either config=RunConfig(...) or the legacy "
                f"keyword arguments ({', '.join(sorted(used))}), not both")
        return config
    if used:
        _warn_legacy(where, list(used))
    return RunConfig(**used)
