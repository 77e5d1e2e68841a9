"""search.batch_ms.serve: mean time of one engine worker call, in ms,
from the benchmark's span that wraps `StaticWorker.search_batch` (it
ends with the results on the host), over the whole window."""


def read(run):
    calls = run.counters.get("calls")
    if not calls:
        return None
    return 1e3 * sum(e - s for s, e, _ in calls) / len(calls)
