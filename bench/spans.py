"""The program's own host spans in a profiler trace, and what they say
about the device's idle stretches.

The executor opens ``exec:pass``, ``exec:predict``, ``exec:wait`` and,
on each worker, ``exec:task`` (stats ``task`` and ``handoff_us``); the
DeepDriveMD payloads open ``ddmd:<kind>`` around each call and, inside
it, ``ddmd:lock`` (the wait for the state's lock) and ``ddmd:block`` (the
wait for the device).  Each host thread is one line of the trace's host
plane, and spans on one line nest.

A program's span is any host annotation named in the program's form, a
lowercase ``<prefix>:`` and then a name, except the harness's own
(``bench:``, ``payload:``); JAX's own host events (``PjitFunction(f)``,
``Foo::Bar``) have another form.

An idle stretch of the device is named by what the threads were in
during it.  At each moment every thread is in its innermost open span,
and the moment takes the name that comes first in ``PRECEDENCE``: a
payload's own host work, then the dispatcher's work, then a worker
outside its payload, then any other program span (taken as host work),
then the waits (lock, device, dispatcher); ``none`` where no thread is
in a span.  A stretch is named by the moment's name that covers most of
it.

``bench/trace.py`` keeps the spans inside ``bench:window`` and the ten
longest idle stretches so named; the functions below read numbers from
the spans for ``bench/metrics`` and ``bench/hunt.py``.
"""

from __future__ import annotations

import collections
import re
from typing import NamedTuple

_PROGRAM = re.compile(r"[a-z][a-z0-9_]*:[^:\s]")
_HARNESS = ("bench:", "payload:")
#: innermost-span names, strongest first; ``ddmd:<kind>`` stands for any
#: ``ddmd:`` span that is not a lock or block wait, ``<other>`` for any
#: span not named here
PRECEDENCE = ("ddmd:<kind>", "exec:pass", "exec:predict", "exec:task",
              "<other>", "ddmd:lock", "ddmd:block", "exec:wait")
WAITS = ("ddmd:lock", "ddmd:block")


class HostSpan(NamedTuple):
    start: float
    end: float
    name: str
    thread: int
    stats: dict


def _rank(name: str) -> int:
    if name.startswith("ddmd:") and name not in WAITS:
        return 0
    if name in PRECEDENCE:
        return PRECEDENCE.index(name)
    return PRECEDENCE.index("<other>")


def is_program_span(name: str) -> bool:
    return (_PROGRAM.match(name) is not None
            and not name.startswith(_HARNESS))


def program_spans(plane, lo: float, hi: float) -> list[HostSpan]:
    """The program's spans on a host plane that overlap ``[lo, hi)``."""
    out = []
    for thread, line in enumerate(plane.lines):
        for e in line.events:
            if is_program_span(e.name):
                s, d = e.start_ns, e.start_ns + e.duration_ns
                if d > lo and s < hi:
                    out.append(HostSpan(s, d, e.name, thread,
                                        dict(e.stats)))
    return out


def _moment(open_by_thread: dict) -> str:
    """The name of a moment: the strongest innermost span over threads
    (payload kinds that tie are joined, sorted, by ``+``)."""
    inner = [stack[-1].name for stack in open_by_thread.values() if stack]
    if not inner:
        return "none"
    best = min(_rank(n) for n in inner)
    names = sorted({n for n in inner if _rank(n) == best})
    if best == 0:
        return "ddmd:" + "+".join(n[len("ddmd:"):] for n in names)
    return names[0]


def named_gaps(busy, window, spans: list[HostSpan], n: int = 10) -> list:
    """The ``n`` longest idle stretches of the device in ``window``, given
    its busy intervals: ``(name, ns, {moment name: ns})`` each."""
    lo, hi = window
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    # sweep gap and span boundaries in time order: at one instant ends go
    # before starts, and an enclosing span opens before what it encloses
    points = []
    for k, (s, e) in enumerate(gaps):
        points += [(s, 1, 0.0, -1, k), (e, 0, 0.0, -1, k)]
    for sp in spans:
        points += [(sp.start, 1, -sp.end, sp.thread, sp),
                   (sp.end, 0, 0.0, sp.thread, sp)]
    points.sort(key=lambda p: p[:3])
    stacks: dict = collections.defaultdict(list)
    per_gap: dict = collections.defaultdict(collections.Counter)
    in_gap, prev = None, lo
    for t, starts, _, thread, ref in points:
        if in_gap is not None and t > prev:
            per_gap[in_gap][_moment(stacks)] += t - prev
        prev = t
        if thread < 0:
            in_gap = ref if starts else None
        elif starts:
            stacks[thread].append(ref)
        else:
            stacks[thread].remove(ref)
    out = [(c.most_common(1)[0][0], gaps[k][1] - gaps[k][0], dict(c))
           for k, c in per_gap.items()]
    return sorted(out, key=lambda g: -g[1])[:n]


def _total(spans, pred) -> float:
    return sum(s.end - s.start for s in spans if pred(s.name))


def engine_ms_per_task(spans: list[HostSpan]) -> float | None:
    """Dispatcher passes and re-predictions, ms per task the passes
    started."""
    tasks = sum(s.stats["started"] for s in spans if s.name == "exec:pass")
    if not tasks:
        return None
    ns = _total(spans, lambda n: n in ("exec:pass", "exec:predict"))
    return ns / tasks / 1e6


def task_handoff_ms(spans: list[HostSpan]) -> float | None:
    """Mean time from a task's submit to its worker's first line, ms."""
    us = [s.stats["handoff_us"] for s in spans if s.name == "exec:task"]
    return sum(us) / len(us) / 1e3 if us else None


def wait_timeouts(spans: list[HostSpan]) -> int:
    """Dispatcher waits that ran out their timeout, no completion having
    woken them."""
    return sum(s.name == "exec:wait" and s.stats["timeout"] == 1
               for s in spans)


def lock_wait_share(spans: list[HostSpan]) -> float | None:
    """Percent of the payload calls' time spent waiting for the lock."""
    calls = _total(spans, lambda n: n.startswith("ddmd:")
                   and n not in WAITS)
    if not calls:
        return None
    return 100.0 * _total(spans, lambda n: n == "ddmd:lock") / calls
