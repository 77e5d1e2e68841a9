"""search_expand_roofline.serve: the fused beam-expansion kernel's share
of its roofline in the open-loop serving cell, in %: the least time the
traced engine calls' expansions need (bench/roofline.py
`search_expand`) over the kernel's device time.  The engine's worker
does not hand back `n_expanded`, so the benchmark counts it after the
window for the traced calls' real queries (bench/drive.py
`_recount_fresh`); padding rows are work no request needs."""
from bench import roofline

PATTERN = r"^search_expand_pallas\b"


def read(run):
    fresh = run.counters.get("fresh_by_call")
    if run.trace is None or not fresh:
        return None
    sec, count = run.trace.op_seconds(PATTERN)
    if not count:
        return None
    peak = roofline.peaks(run.device_kind)
    d = run.config["d"]
    need = sum(roofline.least_seconds(
        *roofline.search_expand(f, q, d), peak) for f, q in fresh.values())
    return 100.0 * need / sec
