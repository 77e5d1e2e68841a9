"""engine.occupancy.serve: the engine's mean batch occupancy over the
window, in %: real requests over padded bucket rows, averaged over
batches (`EngineStats.mean_occupancy`)."""


def read(run):
    occ = run.counters.get("mean_occupancy")
    return None if not occ else 100.0 * occ
