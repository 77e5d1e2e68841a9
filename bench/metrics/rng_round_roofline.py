"""rng_round_roofline: the fused propagation round's share of its
roofline, in %: the least time its work needs (bench/roofline.py
`rng_round`, at the published peaks) over the kernel's device time.

One kernel call handles one chunk of vertices; a build makes
t1·t2·ceil(n / chunk) of them.  The kernel's ops carry the name of the
function that calls `pallas_call` (`rng_round_pallas.<k>`)."""
from bench import roofline

PATTERN = r"^rng_round_pallas\b"


def read(run):
    if run.trace is None:
        return None
    sec, count = run.trace.op_seconds(PATTERN)
    if not count:
        return None
    cfg, b = run.config, run.config["build"]
    n, d, chunk = cfg["n"], cfg["d"], run.counters["chunk"]
    peak = roofline.peaks(run.device_kind)
    sizes = [min(chunk, n - lo) for lo in range(0, n, chunk)]
    per_round = sum(roofline.least_seconds(
        *roofline.rng_round(c, n, d, b["r"], b["pairs_per_vertex"]), peak)
        for c in sizes)
    calls_per_round = len(sizes)
    return 100.0 * per_round * count / calls_per_round / sec
