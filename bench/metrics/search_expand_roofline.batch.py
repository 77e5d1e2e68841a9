"""search_expand_roofline.batch: the fused beam-expansion kernel's share
of its roofline in the closed-loop batch cell, in %: the least time the
traced calls' expansions need (bench/roofline.py `search_expand`, from
each call's `n_expanded` as `search()` returns it) over the kernel's
device time.  The traced window holds whole calls only."""
from bench import roofline

PATTERN = r"^search_expand_pallas\b"


def read(run):
    if run.trace is None:
        return None
    sec, count = run.trace.op_seconds(PATTERN)
    if not count:
        return None
    peak = roofline.peaks(run.device_kind)
    d = run.config["d"]
    need = sum(roofline.least_seconds(
        *roofline.search_expand(int(nexp.sum()), len(nexp), d), peak)
        for nexp in run.counters["n_expanded"])
    return 100.0 * need / sec
