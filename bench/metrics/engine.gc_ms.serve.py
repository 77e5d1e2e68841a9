"""engine.gc_ms.serve: host garbage collection, in ms per second of the
window: the program's `host.gc` spans, summed over the part of the
window from the first engine step's start to the last one's end, over
that part's length."""
from bench import spans


def read(run):
    steps = spans.records("ann_engine.step")
    if not steps:
        return None
    lo, hi = min(s.t0 for s in steps), max(s.t1 for s in steps)
    if hi <= lo:
        return None
    gc = sum(max(0.0, min(r.t1, hi) - max(r.t0, lo))
             for r in spans.records("host.gc"))
    return 1e3 * gc / (hi - lo)
