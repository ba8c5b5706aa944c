"""The program's own host spans in a profiler trace, and what they say
about the device's idle stretches.

The executor opens ``exec:pass``, ``exec:predict``, ``exec:wait`` and,
on each worker, ``exec:task`` (stats ``task`` and ``handoff_us``); the
DeepDriveMD payloads open ``ddmd:<kind>`` around each call and, inside
it, ``ddmd:lock`` (the wait for the state's lock) and ``ddmd:block`` (the
wait for the device).  Each host thread is one line of the trace's host
plane, and spans on one line nest.

An idle stretch of the device is named by what the threads were in
during it.  At each moment every thread is in its innermost open span,
and the moment takes the name that comes first in ``PRECEDENCE``: a
payload's own host work, then the dispatcher's work, then a worker
outside its payload, then the waits (lock, device, dispatcher); ``none``
where no thread is in a span.  A stretch is named by the moment's name
that covers most of it.

``reduce`` gives, from one trace: the spans inside ``bench:window``, the
ten longest idle stretches so named, three numbers that read the spans
(``engine_ms_per_task``, ``task_handoff_ms``, ``lock_wait_share``) and
the count of dispatcher waits that timed out.  ``bench/hunt.py`` prints them; the per-layer
readers of ``bench/run.py`` do not read them (PERF.md, Open questions).
"""

from __future__ import annotations

import collections
from typing import NamedTuple

from bench.trace import WINDOW, _clip, _union

PROGRAM = ("exec:", "ddmd:")
#: innermost-span names, strongest first; ``ddmd:<kind>`` stands for any
#: ``ddmd:`` span that is not a lock or block wait
PRECEDENCE = ("ddmd:<kind>", "exec:pass", "exec:predict", "exec:task",
              "ddmd:lock", "ddmd:block", "exec:wait")
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
    return PRECEDENCE.index(name)


def program_spans(plane, lo: float, hi: float) -> list[HostSpan]:
    """The program's spans on a host plane that overlap ``[lo, hi)``."""
    out = []
    for thread, line in enumerate(plane.lines):
        for e in line.events:
            if e.name.startswith(PROGRAM):
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


def device_busy(plane, lo: float, hi: float) -> list:
    """The union of the device's operations inside ``[lo, hi)``, as
    ``bench/trace.py`` computes it for ``busy_ns``."""
    ops = [ln for ln in plane.lines if ln.name == "XLA Ops"][0]
    return _clip(_union((e.start_ns, e.start_ns + e.duration_ns)
                        for e in ops.events
                        if lo < e.start_ns + e.duration_ns
                        and e.start_ns < hi), lo, hi)


def reduce(path: str, device: str = "/device:TPU:0") -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host = data.find_plane_with_name("/host:CPU")
    window = next(((e.start_ns, e.start_ns + e.duration_ns)
                   for line in host.lines for e in line.events
                   if e.name == WINDOW), None)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW} annotation")
    spans = program_spans(host, *window)
    busy = device_busy(data.find_plane_with_name(device), *window)
    return dict(
        spans=spans,
        named_gaps=named_gaps(busy, window, spans),
        engine_ms_per_task=engine_ms_per_task(spans),
        task_handoff_ms=task_handoff_ms(spans),
        lock_wait_share=lock_wait_share(spans),
        wait_timeouts=wait_timeouts(spans))
