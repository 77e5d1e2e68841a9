"""engine.wait_ms.serve: mean time, in ms, a request waited in the
engine's queue, from `submit()` to the start of the batch that serves it,
on the engine's own clock: every `wait_s` of the program's
`ann_engine.form` spans in the window."""
from bench import spans


def read(run):
    waits = spans.mean(w for r in spans.records("ann_engine.form")
                       for w in r.counts["wait_s"])
    return None if waits is None else 1e3 * waits
