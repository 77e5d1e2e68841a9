"""search.expand_util.batch: the share, in %, of beam-loop work not spent
waiting for the slowest query: per call, the sum of `n_expanded` over
Q times its largest, from `search()`'s own result, averaged over the
window's calls (ROADMAP A2)."""


def read(run):
    calls = run.counters.get("n_expanded")
    if not calls:
        return None
    util = [nexp.sum() / (len(nexp) * nexp.max()) for nexp in calls
            if nexp.max() > 0]
    return 100.0 * sum(util) / len(util) if util else None
