"""Program spans and counters, kept in memory, off by default.

A span is a named interval of the host's work with the span that encloses
it; a counter adds numbers to the innermost open span. Recording is off
until :func:`enable` and ends with :func:`collect`, which hands the
records over. Only this API turns it on.

While off, :func:`span` returns one shared no-op object: it reads no clock
and records nothing, and :func:`count` returns at once. While on, spans are
stamped with ``time.perf_counter_ns()``; :func:`enable` takes one
``(perf_counter_ns, time_ns)`` pair, so ``start_ns + wall_minus_perf_ns``
is a stamp on the wall clock (``time.time_ns()``), the clock on which a
profiler trace gives its start. Recording is for one thread: spans opened
by other threads at the same time would nest wrongly.

While on, every backend compile counts as ``compiles`` on the outermost
open span, which shows which call recompiled.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class _Noop:
    """What :func:`span` returns while recording is off."""

    __slots__ = ()
    start_ns = end_ns = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def count(self, name: str, n: int = 1) -> None:
        pass


NOOP = _Noop()


class Span:
    """One open span of a recording; a record once it is closed."""

    __slots__ = ("name", "start_ns", "end_ns", "parent", "call", "counters",
                 "_rec")

    def __init__(self, rec: "_Recorder", name: str, start_ns: Optional[int]):
        self._rec = rec
        self.name = name
        self.start_ns = start_ns
        self.end_ns: Optional[int] = None
        self.parent: Optional[int] = None
        self.call = 0
        self.counters: Dict[str, int] = {}

    def __enter__(self):
        rec = self._rec
        if rec.stack:
            self.parent = rec.stack[-1]
            self.call = rec.spans[self.parent].call
        else:
            self.call = rec.calls
            rec.calls += 1
        rec.stack.append(len(rec.spans))
        rec.spans.append(self)
        if self.start_ns is None:
            self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        self._rec.stack.pop()
        return False

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def record(self) -> dict:
        return {"name": self.name, "start_ns": self.start_ns,
                "end_ns": self.end_ns, "parent": self.parent,
                "call": self.call, "counters": dict(self.counters)}


class _Recorder:
    def __init__(self):
        perf, wall = time.perf_counter_ns(), time.time_ns()
        self.wall_minus_perf_ns = wall - perf
        self.spans: List[Span] = []
        self.stack: List[int] = []       # indices of the open spans
        self.calls = 0

    def on_event(self, event: str, secs: float, **kwargs) -> None:
        if event == BACKEND_COMPILE and self.stack:
            self.spans[self.stack[0]].count("compiles")


_recorder: Optional[_Recorder] = None


def enable() -> None:
    """Start recording (a recording already on is discarded)."""
    import jax

    global _recorder
    if _recorder is not None:
        collect()
    _recorder = _Recorder()
    jax.monitoring.register_event_duration_secs_listener(_recorder.on_event)


def collect() -> dict:
    """Stop recording and return what was recorded: ``spans``, a list of
    records in the order they opened, each with ``name``, ``start_ns``,
    ``end_ns``, ``parent`` (the index of the enclosing span, or None),
    ``call`` (one id per root span, shared by all spans under it),
    and ``counters``; and ``wall_minus_perf_ns``. Spans still
    open have ``end_ns`` None. Empty when recording was off."""
    import jax

    global _recorder
    rec, _recorder = _recorder, None
    if rec is None:
        return {"wall_minus_perf_ns": 0, "spans": []}
    jax.monitoring.unregister_event_duration_listener(rec.on_event)
    return {"wall_minus_perf_ns": rec.wall_minus_perf_ns,
            "spans": [s.record() for s in rec.spans]}


def span(name: str, start_ns: Optional[int] = None):
    """A context manager that records ``name`` from entry to exit, nested
    in the innermost open span. ``start_ns`` stamps the start with a
    ``perf_counter_ns`` reading the caller has already taken."""
    rec = _recorder
    if rec is None:
        return NOOP
    return Span(rec, name, start_ns)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span."""
    rec = _recorder
    if rec is None or not rec.stack:
        return
    rec.spans[rec.stack[-1]].count(name, n)
