"""Named host spans on the profiler's timeline.

``span(name)`` enters ``jax.profiler.TraceAnnotation(name)``: under a
profiler trace it is a host event on the same timeline as the device ops,
otherwise one no-op TraceMe.  Inside ``recording()`` each span is also
kept in memory as a ``Span`` on ``time.time_ns()``, the clock the
profiler stamps host events with, for phases no trace covers (set-up).
Outside ``recording()`` nothing is kept.
"""
from __future__ import annotations

import contextlib
import time
from typing import NamedTuple

import jax


class Span(NamedTuple):
    name: str
    parent: str | None   # the innermost recorded span around this one
    start_ns: int
    end_ns: int


class _Recorder:
    def __init__(self):
        self.spans: list[Span] = []
        self.open: list[str] = []     # names of the spans entered, in order


_active: _Recorder | None = None      # the innermost recording() block


class _Recorded:
    def __init__(self, name: str, recorder: _Recorder):
        self.name, self.recorder = name, recorder
        self.annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.annotation.__enter__()
        rec = self.recorder
        self.parent = rec.open[-1] if rec.open else None
        rec.open.append(self.name)
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, *exc):
        end_ns = time.time_ns()
        self.recorder.open.pop()
        self.recorder.spans.append(Span(self.name, self.parent,
                                        self.start_ns, end_ns))
        return self.annotation.__exit__(*exc)


def span(name: str):
    """A context manager that marks ``name`` on the profiler's host
    timeline, and records it inside ``recording()``."""
    if _active is None:
        return jax.profiler.TraceAnnotation(name)
    return _Recorded(name, _active)


@contextlib.contextmanager
def recording():
    """Keep every span entered inside the block; yields the list they are
    appended to as they end."""
    global _active
    outer, _active = _active, _Recorder()
    try:
        yield _active.spans
    finally:
        _active = outer
