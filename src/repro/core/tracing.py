"""Named host spans on the profiler's clock.

``span(name, **stats)`` opens a ``jax.profiler.TraceAnnotation``.  While
a profiler trace runs, the span and its keyword stats land in that trace
beside the device's programs and operations, on the same clock, so no
alignment code is needed; with no trace running a span costs about a
microsecond.  ``set_metadata(**stats)`` on the open span adds stats known
only at its end.

``core`` imports without JAX (``model_batch.jax_available``), so JAX is
looked up at the first span; without it every span is a no-op.

Span names in use, and what reads them (PERF.md, section 3):

- ``exec:pass``, ``exec:predict``, ``exec:wait``, ``exec:task``:
  ``RealExecutor``'s dispatcher pass, re-prediction, wait, and a worker's
  run of one task attempt;
- ``ddmd:<kind>``, ``ddmd:lock``, ``ddmd:block``: a DeepDriveMD payload
  call, its wait for the shared state's lock, and its wait for the device
  (``launch/ddmd.py``).
"""

from __future__ import annotations

import functools

__all__ = ["span"]


class _NoSpan:
    """The span where JAX is missing."""

    def __init__(self, name: str, **stats):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats) -> None:
        pass


@functools.cache
def _annotation():
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return _NoSpan
    return TraceAnnotation


def span(name: str, **stats):
    """A context manager that records ``name`` with ``stats`` in the
    running profiler trace, if there is one."""
    return _annotation()(name, **stats)
