"""Reduction of one profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

The device plane's ``XLA Modules`` line holds one event per program run,
named ``jit_<function>(<id>)``; its ``XLA Ops`` line holds the HLO
operations, nested (a ``while`` spans its body), each named by its HLO
text, ``%<op> = ...``.  The host plane holds the harness's annotations:
``bench:window`` around the traced instances and ``payload:<kind>``
around each payload call.  Host and device events share the trace's
clock (nanoseconds from its start).

Busy time is the union of the operations' intervals inside the window.
An idle stretch is attributed to what the host was doing: ``dispatch``
where no payload call was open, ``payload:<kinds>`` (the open kinds,
sorted, joined by ``+``) where one or more were; ``bench/spans.py`` also
names each by the program's own host spans.

Device time by ``jax.named_scope``: the trace does not carry the scope
(an operation's event holds its HLO text without ``metadata``), so it is
taken from the programs themselves.  Given the compiled HLO text of each
program (``Compiled.as_text()``), every operation is matched by its
instruction name, within its program, to the ``op_name`` of that
instruction's metadata, and its self time counts under that name's
scope path.  A fusion counts under its own ``op_name``, which XLA
usually takes from the fusion's root, so the operations fused into it
from other scopes count under the root's.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re

from bench import spans as program

WINDOW = "bench:window"
PAYLOAD = "payload:"
_ID = re.compile(r"\(\d+\)$")
_SUFFIX = re.compile(r"\.\d+$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_WRAPPED = re.compile(r"\w+\((.*)\)")


@dataclasses.dataclass
class Reduced:
    window_ns: float
    busy_ns: float
    #: program -> [runs, summed device ns]
    programs: dict
    #: kernel -> program -> [calls, summed device ns]
    kernels: dict
    #: "program/op" -> self ns
    op_self_ns: dict
    #: host state -> idle ns
    idle_ns: dict
    #: the longest idle stretches: [(host state, ns)]
    gaps: list
    #: program -> ``jax.named_scope`` path -> [ops, self ns], for the
    #: programs whose HLO text was given
    scopes: dict
    #: the program's host spans inside the window (``bench/spans.py``)
    spans: list
    #: the longest idle stretches named by those spans:
    #: [(name, ns, {moment's name: ns})]
    span_gaps: list


def program_name(module_event_name: str) -> str:
    return _ID.sub("", module_event_name)


def instruction(op_event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return op_event_name.split(" = ", 1)[0].lstrip("%")


def op_name(op_event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    return _SUFFIX.sub("", instruction(op_event_name))


def scope_of(name: str) -> str:
    """The ``jax.named_scope`` path in an instruction's ``op_name``: its
    components between the program's own ``jit(<fn>)`` and the operation,
    each wrapper such as ``jvp(...)`` or ``transpose(...)`` unwrapped to
    the name inside; ``""`` at the program's top level."""
    parts = name.split("/")
    if parts[0].startswith("jit("):
        parts = parts[1:]
    out = []
    for part in parts[:-1]:
        while m := _WRAPPED.fullmatch(part):
            part = m.group(1)
        if part:                              # ``jvp()``: no scope
            out.append(part)
    return "/".join(out)


def instruction_scopes(hlo_text: str) -> tuple[str, dict]:
    """(program, {instruction: scope path}) of one compiled program's HLO
    text; an instruction without an ``op_name`` is at the top level."""
    lines = hlo_text.splitlines()
    program = lines[0].split()[1].rstrip(",")        # "HloModule jit_f, ..."
    out = {}
    for line in lines[1:]:
        head, eq, _ = line.strip().removeprefix("ROOT ").partition(" = ")
        if eq and head.startswith("%"):
            m = _OP_NAME.search(line)
            out[head[1:]] = scope_of(m.group(1)) if m else ""
    return program, out


def _host_spans(plane):
    window, payloads = None, []
    for line in plane.lines:
        for e in line.events:
            if e.name == WINDOW:
                window = (e.start_ns, e.start_ns + e.duration_ns)
            elif e.name.startswith(PAYLOAD):
                payloads.append((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name[len(PAYLOAD):]))
    return window, payloads


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _idle(busy, window, payloads):
    """Idle ns by host state, and each idle stretch's dominant state."""
    lo, hi = window
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    # sweep the gap and payload boundaries in time order
    points = []
    for n, (s, e) in enumerate(gaps):
        points += [(s, 1, "gap", n), (e, 0, "gap", n)]
    for s, e, kind in payloads:
        points += [(s, 1, "pay", kind), (e, 0, "pay", kind)]
    points.sort(key=lambda p: (p[0], p[1]))
    open_kinds: collections.Counter = collections.Counter()
    in_gap = None
    by_state: collections.Counter = collections.Counter()
    per_gap: dict = collections.defaultdict(collections.Counter)
    prev = lo
    for t, starts, what, ref in points:
        if in_gap is not None and t > prev:
            kinds = sorted(k for k, c in open_kinds.items() if c > 0)
            state = PAYLOAD + "+".join(kinds) if kinds else "dispatch"
            by_state[state] += t - prev
            per_gap[in_gap][state] += t - prev
        prev = t
        if what == "gap":
            in_gap = ref if starts else None
        else:
            open_kinds[ref] += 1 if starts else -1
    longest = sorted(((c.most_common(1)[0][0], gaps[n][1] - gaps[n][0])
                      for n, c in per_gap.items()), key=lambda g: -g[1])
    return dict(by_state), longest[:10]


def reduce(path: str, hlo_texts=(),
           device: str = "/device:TPU:0") -> Reduced:
    """The trace at ``path``, reduced; ``hlo_texts``, the compiled HLO
    text of the programs whose device time is wanted by named scope."""
    from jax.profiler import ProfileData

    scope_at = dict(instruction_scopes(t) for t in hlo_texts)

    data = ProfileData.from_file(path)
    host = data.find_plane_with_name("/host:CPU")
    window, payloads = _host_spans(host)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW} annotation")
    lo, hi = window
    dev = data.find_plane_with_name(device)
    lines = {ln.name: ln for ln in dev.lines}
    modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                      program_name(e.name))
                     for e in lines["XLA Modules"].events
                     if e.start_ns < hi and e.start_ns + e.duration_ns > lo)
    programs: dict = collections.defaultdict(lambda: [0, 0.0])
    for s, e, name in modules:
        programs[name][0] += 1
        programs[name][1] += e - s
    starts = [m[0] for m in modules]

    def program_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return modules[i][2] if i >= 0 and t < modules[i][1] else "?"

    kernels: dict = collections.defaultdict(
        lambda: collections.defaultdict(lambda: [0, 0.0]))
    self_ns: collections.Counter = collections.Counter()
    scopes: dict = collections.defaultdict(
        lambda: collections.defaultdict(lambda: [0, 0.0]))

    def close(key, child):
        name, d, prog, scope = key
        self_ns[name] += d - child
        if scope is not None:
            sc = scopes[prog][scope]
            sc[0] += 1
            sc[1] += d - child

    ops = sorted((e.start_ns, -e.duration_ns, e.name)
                 for e in lines["XLA Ops"].events
                 if lo < e.start_ns + e.duration_ns and e.start_ns < hi)
    spans = []
    stack: list = []          # open ops: [end, key, child ns]
    for s, d, full in ops:
        d = -d
        while stack and stack[-1][0] <= s:
            _, key, child = stack.pop()
            close(key, child)
        if stack:
            stack[-1][2] += d
        name = op_name(full)
        prog = program_at(s)
        scope = scope_at.get(prog, {}).get(instruction(full))
        stack.append([s + d, (f"{prog}/{name}", d, prog, scope), 0.0])
        spans.append((s, s + d))
        if "pallas" in name:
            k = kernels[name][prog]
            k[0] += 1
            k[1] += d
    for _, key, child in stack:
        close(key, child)
    busy = _clip(_union(spans), lo, hi)
    idle, gaps = _idle(busy, window, [p for p in payloads
                                      if p[1] > lo and p[0] < hi])
    host_spans = program.program_spans(host, lo, hi)
    return Reduced(window_ns=hi - lo,
                   busy_ns=sum(e - s for s, e in busy),
                   programs={k: list(v) for k, v in programs.items()},
                   kernels={k: {p: list(c) for p, c in v.items()}
                            for k, v in kernels.items()},
                   op_self_ns=dict(self_ns), idle_ns=idle, gaps=gaps,
                   scopes={p: {k: list(v) for k, v in sc.items()}
                           for p, sc in scopes.items()},
                   spans=host_spans,
                   span_gaps=program.named_gaps(busy, window, host_spans))


def breakdown(r: Reduced) -> dict:
    """The result line's ``breakdown``: the device operations with the
    most self time, and the longest idle stretches by host state and by
    the program's spans."""
    ops = sorted(r.op_self_ns.items(), key=lambda kv: -kv[1])[:10]
    return dict(device_ops=[[k, v / 1e9] for k, v in ops],
                idle_gaps=[[k, v / 1e9] for k, v in r.gaps],
                span_gaps=[[k, v / 1e9] for k, v, _ in r.span_gaps])
