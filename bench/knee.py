"""Find the knee of an open-loop cell: the highest rate it sustains.

    python3 bench/knee.py --workload sift1m.serve --seed 5 --seconds 10 \
        --rates 25 50 100 200

Builds the cell's set-up once, then runs one window per offered rate and
prints a JSON line for each: the rate offered and achieved, latency
percentiles from due times, and whether the backlog grew through the
window (the last tenth of the requests waited much longer than the
first).  Run it once when a serving cell is defined, and set the cell's
rate from it; the benchmark's own runs never search for a rate.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    from bench import drive, harness
    from repro.launch.cache import enable_compile_cache
    spec = harness.resolve(args.workload)
    if spec.traffic["mode"] != "open_loop":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("knee: JAX found no TPU", file=sys.stderr)
        return 2
    run = harness.Run(spec, args.seed, args.seconds, False, T_START)
    state = drive.serve_setup(run)
    for rate in args.rates:
        w = drive.serve_window(run, *state, rate=rate)
        lat = (w["done"] - w["due"]) * 1e3
        n = len(lat)
        tenth = max(1, n // 10)
        print(json.dumps({
            "offered_qps": rate, "requests": n,
            "achieved_qps": n / float(np.max(w["done"])),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": drive._p95(lat), "max_ms": float(lat.max()),
            "first_tenth_mean_ms": float(lat[:tenth].mean()),
            "last_tenth_mean_ms": float(lat[-tenth:].mean()),
            "mean_occupancy": w["occupancy"],
            "generator_late_ms_max": float(w["late"].max() * 1e3),
            "compiles_in_window": run.compiles_in_window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
