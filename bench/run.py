"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload sift1m.build --seed 7 --seconds 30 --trace 0

The cells are BENCHMARK.json's `workloads`.  The run makes its data from
--seed, warms up, measures for --seconds, checks what the timed path
returned against the plain reference, and prints one JSON line last on
standard output.  --trace 1 reports the per-layer metrics from a device
trace instead of the end-to-end ones.  With no TPU, or fewer chips than
the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.resolve(args.workload)

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    devs = jax.devices()
    chips = spec.cell["chips"]
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform!r} device(s)", file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips}
    line = harness.execute(spec, args.seed, args.seconds, bool(args.trace),
                           T_START, device)
    harness.report(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
