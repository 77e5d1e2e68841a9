"""Hold the program's serving spans against the benchmark's own numbers,
in one traced run of an open-loop cell.

    python3 bench/span_check.py --workload sift1m.serve --seed 7 --seconds 10

Runs the cell once with the profiler on, as `bench/run.py --trace 1`
does, and prints one JSON line: `correct`, `query_p95_ms`, the worker
calls per second and the longest call, every per-layer metric of the
cell, and these cross-checks:

  dispatch_readback_ms  the mean of `worker.dispatch` + `worker.readback`
                        over the query steps that hold the benchmark's
                        worker calls, beside `search.batch_ms.serve` (the
                        benchmark's span around the same calls);
  n_expanded            over the calls that the benchmark counts again
                        after the window (`_recount_fresh`), the
                        `worker.readback` counts summed over each batch's
                        real rows, and in how many calls the two agree;
  idle_*_steps_pct      the device-idle share of the traced window inside
                        the program's `ann_engine.step` spans and between
                        them, which add up to `device.idle.serve`;
  longest_step          the longest query step taken apart: its spans,
                        the host GC inside it, and its largest
                        `n_expanded` beside the median of that over steps.

Without the program's spans (a program older than them, or one whose
spans are off) the cross-checks are null and the rest is printed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spans  # noqa: E402
from bench import trace as T  # noqa: E402


def _ms(seconds):
    return None if seconds is None else 1e3 * seconds


def _overlap(rec, lo, hi) -> float:
    return max(0.0, min(rec.t1, hi) - max(rec.t0, lo))


def crosschecks(run) -> dict:
    """The cross-checks of one traced open-loop run (see the module)."""
    steps = spans.query_steps()
    order = sorted(steps, key=lambda i: steps[i].t0)
    forms = spans.by_step("ann_engine.form")
    disp = spans.by_step("worker.dispatch")
    rb = spans.by_step("worker.readback")
    calls = run.counters.get("calls") or []
    # the query step that holds each of the benchmark's worker calls
    t0s = [steps[i].t0 for i in order]
    step_of = []
    for s, e, _ in calls:
        j = bisect.bisect_right(t0s, s) - 1
        step_of.append(order[j] if j >= 0 and steps[order[j]].t1 >= e
                       else None)
    out = {"query_steps": len(order), "worker_calls": len(calls),
           "calls_in_steps": sum(i is not None for i in step_of)}

    out["dispatch_readback_ms"] = _ms(spans.mean(
        disp[i].seconds + rb[i].seconds for i in step_of
        if i in disp and i in rb))

    pairs = []
    for c, (fresh, n_real) in sorted(
            (run.counters.get("fresh_by_call") or {}).items()):
        sid = step_of[c] if c < len(step_of) else None
        nexp = rb[sid].counts.get("n_expanded") if sid in rb else None
        if nexp is None or sid not in forms:
            continue
        real = forms[sid].counts["n_real"]
        pairs.append((fresh, sum(nexp[:real]), n_real == real))
    out["n_expanded"] = None if not pairs else {
        "calls": len(pairs),
        "equal": sum(a == b and same for a, b, same in pairs),
        "recount_total": sum(a for a, _, _ in pairs),
        "program_total": sum(b for _, b, _ in pairs)}

    out["idle_in_steps_pct"] = out["idle_between_steps_pct"] = None
    host = run.trace.host_spans("ann_engine.step") if run.trace else []
    if host:
        lo, hi = run.trace.window
        gaps = T.gaps_ns(run.trace.devices[0], lo, hi)
        idle = sum(e - s for s, e in gaps)
        inside = sum(T.union_ns(host, s, e) for s, e in gaps)
        out["idle_in_steps_pct"] = 100.0 * inside / (hi - lo)
        out["idle_between_steps_pct"] = 100.0 * (idle - inside) / (hi - lo)

    out["longest_step"] = None
    if order:
        sid = max(order, key=lambda i: steps[i].seconds)
        step = steps[sid]
        peaks = [max(rb[i].counts["n_expanded"]) for i in order
                 if i in rb and rb[i].counts.get("n_expanded")]
        parts = {}
        for name, recs in (("ann_engine.form", forms), ("worker.dispatch", disp),
                           ("worker.readback", rb)):
            parts[name] = _ms(recs[sid].seconds) if sid in recs else None
        out["longest_step"] = dict(
            ms=_ms(step.seconds), **parts,
            gc_ms=_ms(sum(_overlap(g, step.t0, step.t1)
                          for g in spans.records("host.gc"))),
            max_n_expanded=max(rb[sid].counts.get("n_expanded") or [0])
            if sid in rb else None,
            median_max_n_expanded=statistics.median(peaks) if peaks else None)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import check, drive, harness
    from repro.launch.cache import enable_compile_cache
    spec = harness.resolve(args.workload)
    if spec.traffic["mode"] != "open_loop":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("span_check: JAX found no TPU", file=sys.stderr)
        return 2
    run = harness.Run(spec, args.seed, args.seconds, True, T_START,
                      dev.device_kind, spec.cell["chips"])
    out = drive.open_loop(run)
    correct, _ = check.verdict(out["numbers"], spec.config["limits"])
    calls = run.counters["calls"]
    print(json.dumps({
        "seed": args.seed, "correct": correct, "failed": out["failed"],
        "query_p95_ms": out["metrics"]["query_p95_ms"],
        "calls_per_s": len(calls) / (calls[-1][1] - calls[0][0]),
        "longest_call_ms": 1e3 * max(e - s for s, e, _ in calls),
        **out["extra"],
        "metrics": {m["name"]: m["read"](run) for m in spec.per_layer},
        "crosschecks": crosschecks(run)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
