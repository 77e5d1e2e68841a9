"""engine.queue_ms.serve: mean time, in ms, from a request's due time to
the start of the worker call that serves it, over every answered request
of the window (the benchmark's spans)."""


def read(run):
    c = run.counters
    calls, due, call_of = c.get("calls"), c.get("due"), c.get("call_of")
    if not calls:
        return None
    waits = [calls[j][0] - t for t, j in zip(due, call_of) if j >= 0]
    return 1e3 * sum(waits) / len(waits) if waits else None
