"""The program's own spans (`repro.obs`), as the serving cell's readers
see them.

A traced run has the profiler on for its whole window, and the program
records its spans only while the profiler captures, so the ring of
`repro.obs` records holds that window's spans.  Where the program has no
`repro.obs`, or it recorded nothing, every function here returns an
empty result, and the readers that use them report nothing.
"""
from __future__ import annotations


def records(name: str | None = None) -> list:
    """The closed `repro.obs` records, oldest first, of `name` if given."""
    try:
        from repro import obs
    except ImportError:
        return []
    return [r for r in obs.records(name) if r.t1 is not None]


def query_steps() -> dict:
    """{id: record} of the engine steps that ran a query batch."""
    return {r.id: r for r in records("ann_engine.step")
            if r.counts.get("kind") == "query"}


def by_step(name: str) -> dict:
    """{step id: record} of the `name` records directly inside a query
    step (each such step holds one of each)."""
    steps = query_steps()
    return {r.parent: r for r in records(name) if r.parent in steps}


def mean(values) -> float | None:
    values = list(values)
    return sum(values) / len(values) if values else None
