"""The window arithmetic, the DAG a traffic file gives, and the
schedule check."""

import json
from pathlib import Path

import pytest

from bench import check, harness
from bench.harness import Span

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def test_a_cut_instance_is_not_counted():
    runs = [(0.0, 1.0, 48), (1.0, 2.5, 48), (2.5, 4.2, 48)]
    m = harness.window_metrics(runs, deadline=4.0)
    assert m["instances"] == 2 and m["tasks"] == 96
    assert m["wall_s"] == 2.5
    assert m["makespan_s"] == 1.25


def test_no_whole_instance_gives_no_rate():
    m = harness.window_metrics([(0.0, 5.0, 48)], deadline=4.0)
    assert m["instances"] == 0 and "makespan_s" not in m


def _dag(name):
    traffic = json.loads((TRAFFIC / f"{name}.json").read_text())
    return traffic, harness.instance_dag(traffic,
                                         lambda kind, n, k: (lambda i: None))


def test_ddmd_dag_is_fig_3a():
    traffic, g = _dag("ddmd")
    assert harness.tasks_per_instance(traffic) == 48
    assert sum(ts.num_tasks for ts in g.nodes.values()) == 48
    assert list(g.parents("simul1")) == ["simul0"]
    assert list(g.parents("train2")) == ["aggre2"]
    assert sorted(g.parents("aggre0")) == ["simul0"]
    assert "simul3" not in g.nodes


def _spans(order):
    """simul0 (2 tasks) -> aggre0 (1 task), with the given (set, i, start,
    end) calls."""
    return [Span(0, s, i, "k", 0, a, b) for s, i, a, b in order]


@pytest.fixture
def chain():
    from repro.core.dag import DAG, TaskSet

    g = DAG()
    g.add(TaskSet("simul0", 2, 1, 1, 0.0))
    g.add(TaskSet("aggre0", 1, 1, 0, 0.0))
    g.add_edge("simul0", "aggre0")
    return g


def test_schedule_faults(chain):
    ok = _spans([("simul0", 0, 0, 1), ("simul0", 1, 0, 2),
                 ("aggre0", 0, 2, 3)])
    assert check.schedule_faults(chain, ok, 0) == 0
    early = _spans([("simul0", 0, 0, 1), ("simul0", 1, 0, 2),
                    ("aggre0", 0, 1.5, 3)])
    assert check.schedule_faults(chain, early, 0) == 1
    twice = ok + _spans([("simul0", 1, 3, 4)])
    assert check.schedule_faults(chain, twice, 0) >= 1
    missing = ok[1:]
    assert check.schedule_faults(chain, missing, 0) == 1
