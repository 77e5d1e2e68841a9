"""search.readback_ms.serve: mean time, in ms, of the engine worker's
read-back of a batch's results to the host (`worker.readback` spans): the
host waits there for the device to finish the search it dispatched."""
from bench import spans


def read(run):
    t = spans.mean(r.seconds for r in spans.records("worker.readback"))
    return None if t is None else 1e3 * t
