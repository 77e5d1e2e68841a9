"""engine.host_ms.serve: mean host time, in ms, of one query step of the
engine, less the result read-back: each `ann_engine.step` span of kind
query less its `worker.readback` child (what is left is forming the
batch, dispatching it and handing out the results)."""
from bench import spans


def read(run):
    readback = spans.by_step("worker.readback")
    own = spans.mean(
        s.seconds - (readback[i].seconds if i in readback else 0.0)
        for i, s in spans.query_steps().items())
    return None if own is None else 1e3 * own
