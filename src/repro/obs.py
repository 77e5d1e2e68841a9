"""Spans and counters of the serving path, kept only while a profiler captures.

    with obs.span("ann_engine.form") as counts:
        ...
        if counts is not None:          # None: no profiler session
            counts["n_real"] = len(group)

`span(name, **counts)` opens a `jax.profiler.TraceAnnotation(name)`, so
the span lies in the profiler's host plane on the same clock as the
device ops, and appends a `Record` to a bounded in-memory ring.  Records
nest: `parent` is the id of the enclosing open span (the engine is
single-threaded, so one stack is enough).  `t0`/`t1` come from
`time.perf_counter()`.  The caller may add to `counts` while the span is
open.

Nothing is recorded unless a profiler session captures
(`jax.profiler.start_trace` ... `stop_trace`): outside one, `span()`
costs one `TraceAnnotation.is_enabled()` call and returns a shared null
context that yields None.

Host garbage collections become root records named `host.gc`, from a
`gc.callbacks` hook installed when this module is imported; the hook records only while a profiler session captures, and
never touches the span stack, since a collection can start inside any
span.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import itertools
import time

import jax

RING = 1 << 16  # records kept; the oldest are dropped first

_capturing = jax.profiler.TraceAnnotation.is_enabled


@dataclasses.dataclass
class Record:
    name: str
    id: int
    parent: int | None  # id of the enclosing span, None for a root
    t0: float  # time.perf_counter() at open
    t1: float | None = None  # ... at close; None while open
    counts: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


_ring: collections.deque = collections.deque(maxlen=RING)
_stack: list[int] = []  # ids of the open spans, innermost last
_ids = itertools.count()
_NULL = contextlib.nullcontext()


def span(name: str, **counts):
    """A context manager over one span; it yields the record's `counts`
    dict, or None when no profiler session captures."""
    if not _capturing():
        return _NULL
    return _open(name, counts)


@contextlib.contextmanager
def _open(name: str, counts: dict):
    parent = _stack[-1] if _stack else None
    rec = Record(name, next(_ids), parent, time.perf_counter(), counts=counts)
    _ring.append(rec)
    _stack.append(rec.id)
    try:
        with jax.profiler.TraceAnnotation(name):
            yield rec.counts
    finally:
        _stack.pop()
        rec.t1 = time.perf_counter()


def records(name: str | None = None) -> list[Record]:
    """The ring's records, oldest first; only those named `name` if given."""
    return [r for r in _ring if name is None or r.name == name]


_gc_open: list = []  # (record, annotation) of the collection in progress


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if _capturing():
            ann = jax.profiler.TraceAnnotation("host.gc")
            ann.__enter__()
            rec = Record("host.gc", next(_ids), None, time.perf_counter())
            _ring.append(rec)
            _gc_open.append((rec, ann))
    elif _gc_open:
        rec, ann = _gc_open.pop()
        rec.t1 = time.perf_counter()
        ann.__exit__(None, None, None)


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)
