"""device.idle_in_step.serve: the share, in %, of the traced window's
device-idle time (bench/trace.py `gaps_ns`) that lies inside the
program's `ann_engine.step` host spans: idle the engine causes while it
has work, and not idle between steps when no request is pending."""
from bench import trace as T


def read(run):
    if run.trace is None:
        return None
    steps = run.trace.host_spans("ann_engine.step")
    if not steps:
        return None
    gaps = T.gaps_ns(run.trace.devices[0], *run.trace.window)
    idle = sum(e - s for s, e in gaps)
    inside = sum(T.union_ns(steps, s, e) for s, e in gaps)
    return 100.0 * inside / idle if idle else None
