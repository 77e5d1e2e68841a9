"""search.expand_util.serve: the share, in %, of a batch's beam-loop
work that a request needed: per engine batch, the sum of `n_expanded`
over its real rows, over the bucket's rows times the largest
`n_expanded` of any row, padding included; averaged over the window's
batches.  It falls with stragglers and with padding.  The counts are
the `worker.readback` span's `n_expanded` and the `ann_engine.form`
span's `n_real` and `bucket`, under the same `ann_engine.step`."""
from bench import spans


def read(run):
    forms = spans.by_step("ann_engine.form")
    util = []
    for sid, rb in spans.by_step("worker.readback").items():
        nexp, form = rb.counts.get("n_expanded"), forms.get(sid)
        if not nexp or form is None or max(nexp) <= 0:
            continue
        c = form.counts
        util.append(sum(nexp[:c["n_real"]]) / (c["bucket"] * max(nexp)))
    u = spans.mean(util)
    return None if u is None else 100.0 * u
