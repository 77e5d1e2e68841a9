"""build.sort_share: the share, in %, of the build's device time spent in
XLA sort operations: the request staging of `pools._stage`, two sorts of
N·P elements a round.  Sorts are found by their XLA op name."""

PATTERN = r"^sort\b"


def read(run):
    if run.trace is None:
        return None
    sec, count = run.trace.op_seconds(PATTERN)
    return 100.0 * sec / run.trace.busy_s() if count else None
