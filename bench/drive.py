"""The one general traffic generator, and the runner of each of its modes.

A traffic mix is a JSON file of parameters under `traffic/`; its "mode"
picks the runner here, and the rest are that runner's parameters:

  build        whole `core.build_graph` calls back to back, on corpora
               made before the window, for as long as builds start inside
               it; afterwards the last graph is read through `search()`.
  open_loop    single queries arriving on a schedule (Poisson, optionally
               with on/off bursts) through `serve.ann_engine.AnnEngine`
               with a `StaticWorker`, over a graph built in set-up; each
               request is timed from its due time.
  closed_loop  `core.search.search` calls over fixed batches of queries,
               each call started when the last one's results are on the
               host, over a graph built in set-up.

Every runner makes its data from the seed, warms up each shape it will
use before the window, and measures the window with the host clock.
"""
from __future__ import annotations

import collections
import sys
import time

import jax
import numpy as np

from bench import check
from bench import data

clock = time.perf_counter


def log(run, msg: str) -> None:
    """A progress line on stderr, stamped with seconds since start."""
    print(f"[{clock() - run.t_start:8.2f} s] {msg}", file=sys.stderr,
          flush=True)


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of an open-loop schedule.

    The gaps in unit-rate time are the n quantiles of an exponential
    distribution, so every seed gets the same set of gaps in another
    order: the seed changes the order of the arrivals, not the work.
    They are mapped onto the rate curve, `rate_qps` with, when "burst" is
    given, `factor` times that rate for the first `on_s` seconds of every
    `period_s`.  The n arrivals fill [0, seconds] exactly.
    """
    rate = float(traffic["rate_qps"])
    grid = np.linspace(0.0, seconds, max(2, int(seconds * 1000) + 1))
    r = np.full(grid.shape, rate)
    burst = traffic.get("burst")
    if burst:
        r = np.where(grid % burst["period_s"] < burst["on_s"],
                     rate * burst["factor"], rate)
    lam = np.concatenate([[0.0], np.cumsum((r[1:] + r[:-1]) / 2
                                            * np.diff(grid))])
    n = max(1, int(round(lam[-1])))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    data.host_rng(seed, 1).shuffle(gaps)
    unit = np.cumsum(gaps) * (lam[-1] / gaps.sum())
    return np.interp(unit, lam, grid)


def _grnnd_config(config: dict):
    from repro.core.grnnd import GRNNDConfig
    return GRNNDConfig(**config["build"])


def _build_key(seed: int, i: int):
    return jax.random.fold_in(data.seed_key(seed), 1000 + i)


def _built_graph(run, x):
    """Set-up: the graph a serving cell serves, built by the program."""
    from repro.core import build_graph
    pool = build_graph(_build_key(run.seed, 0), x, _grnnd_config(run.config))
    ids = jax.block_until_ready(pool).ids
    log(run, f"set-up build of {x.shape[0]} x {x.shape[1]} done")
    return ids


# ------------------------------------------------------------------ build


def build(run) -> dict:
    from repro.core import build_graph
    from repro.core.search import search

    cfg, tr = run.config, run.traffic
    gcfg = _grnnd_config(cfg)
    corpora = [data.corpus(cfg, run.seed, tr["eval_queries"], stream=i)
               for i in range(tr["corpora"])]
    jax.block_until_ready(corpora)
    log(run, f"data: {len(corpora)} corpora of {cfg['n']} x {cfg['d']}")
    # the build program takes t1 and t2 as traced values: t1=1, t2=0 runs
    # the same compiled program over no rounds, which warms it up
    jax.block_until_ready(build_graph(_build_key(run.seed, 0), corpora[0][0],
                                      gcfg._replace(t1=1, t2=0)))
    log(run, "build program warm")
    t0 = run.begin_window()
    run.tracer.start()
    times, pool = [], None
    while not times or clock() - t0 < run.seconds:
        i = len(times)
        x = corpora[i % len(corpora)][0]
        with run.span("build"):
            s = clock()
            pool = jax.block_until_ready(
                build_graph(_build_key(run.seed, i), x, gcfg))
            times.append(clock() - s)
    run.tracer.stop()
    run.end_window()

    log(run, f"window: {len(times)} builds, {times} s")
    x, q = corpora[(len(times) - 1) % len(corpora)]
    del corpora
    n = cfg["n"]
    run.counters.update(
        rounds=gcfg.t1 * (gcfg.t2 + 1) - 1,
        chunk=gcfg.chunk_size or n)
    res = search(x, pool.ids, q, k=tr["k"], ef=tr["ef"])
    rows = data.host_rng(run.seed, 2).choice(
        n, min(n, tr["graph_sample"]), replace=False)
    numbers = check.answers(x, q, res.ids, res.dists, tr["k"])
    numbers.update(check.graph(x, pool.ids, pool.dists, rows))
    log(run, "checked")
    return dict(metrics={"build_vps": len(times) * n / sum(times)},
                attempted=len(times), failed=0, numbers=numbers,
                extra={"build_s": times})


# -------------------------------------------------------------- open loop


class _TimedWorker:
    """Wraps (does not replace) an engine worker: every `search_batch`
    call is a host span, recorded with its bucket size."""

    def __init__(self, worker, run):
        self.worker, self.run = worker, run
        self.calls = []          # [start, end, bucket]

    def search_batch(self, q, **kw):
        with self.run.span("worker.search_batch"):
            s = clock()
            out = self.worker.search_batch(q, **kw)
            self.calls.append([s, clock(), len(q)])
        return out

    def apply_mutation(self, mut):
        return self.worker.apply_mutation(mut)


def serve_setup(run):
    """Set-up of an open-loop cell: corpus, graph, engine, warm buckets."""
    from repro.serve.ann_engine import AnnEngine, EngineConfig, StaticWorker

    cfg, tr = run.config, run.traffic
    x, qpool = data.corpus(cfg, run.seed, cfg["n_queries"])
    graph = _built_graph(run, x)
    worker = _TimedWorker(StaticWorker(x, graph, visited=tr["visited"]), run)
    eng = AnnEngine(worker, EngineConfig(max_batch=tr["max_batch"]),
                    clock=clock)
    qnp = np.asarray(qpool)
    b = 1
    while b <= eng.cfg.max_batch:        # every Q bucket the engine can form
        rids = [eng.submit(v, k=tr["k"], ef=tr["ef"]) for v in qnp[:b]]
        eng.run()
        for rid in rids:
            eng.take_result(rid)
        b *= 2
    log(run, "engine buckets warm")
    return x, qnp, worker, eng


def serve_window(run, x, qnp, worker, eng, rate=None) -> dict:
    """One open-loop window at the mix's rate (or `rate`): every request
    due in it is answered, and timed from its due time."""
    from repro.serve.ann_engine import EngineSaturated

    tr = dict(run.traffic)
    if rate is not None:
        tr["rate_qps"] = rate
    k, ef = tr["k"], tr["ef"]
    eng.reset_stats()
    worker.calls.clear()
    due = arrivals(tr, run.seconds, run.seed)
    n = len(due)
    order = data.host_rng(run.seed, 3).permutation(len(qnp))
    qidx = order[np.arange(n) % len(qnp)]
    t_trace = max(0.0, (run.seconds - tr["trace_seconds"]) / 2)
    done = np.full(n, np.inf)
    call_of = np.full(n, -1)
    ids = np.full((n, k), -1, np.int32)
    dists = np.full((n, k), np.inf, np.float32)
    late = np.zeros(n)
    pending = collections.deque()        # (request index, rid), FIFO
    traced = [None, None]                # first and last+1 traced call
    i = 0
    t0 = run.begin_window()
    while i < n or pending:
        now = clock() - t0
        while i < n and due[i] <= now:
            late[i] = now - due[i]
            try:
                pending.append((i, eng.submit(qnp[qidx[i]], k=k, ef=ef)))
            except EngineSaturated:
                pass
            i += 1
        if run.tracer.enabled:           # whole engine steps only
            if traced[0] is None and now >= t_trace:
                run.tracer.start()
                traced[0] = len(worker.calls)
            elif (run.tracer.active
                  and now >= t_trace + tr["trace_seconds"]):
                run.tracer.stop()
                traced[1] = len(worker.calls)
        if pending:
            with run.span("engine.step"):
                eng.step()
            for _ in range(eng.log[-1][2]):
                j, rid = pending.popleft()
                r = eng.take_result(rid)
                done[j] = r.t_done - t0
                call_of[j] = len(worker.calls) - 1
                ids[j], dists[j] = r.ids, r.dists
        elif i < n:
            with run.span("replay.wait"):
                time.sleep(max(0.0, due[i] - (clock() - t0)))
    if run.tracer.active:
        run.tracer.stop()
        traced[1] = len(worker.calls)
    run.end_window()
    log(run, f"window: {n} requests, {len(worker.calls)} engine calls")
    return dict(due=due, t0=t0, qidx=qidx, done=done, call_of=call_of,
                ids=ids, dists=dists, late=late, traced=traced,
                occupancy=eng.stats().mean_occupancy)


def open_loop(run) -> dict:
    tr = run.traffic
    x, qnp, worker, eng = serve_setup(run)
    w = serve_window(run, x, qnp, worker, eng)
    due, done, call_of, traced = w["due"], w["done"], w["call_of"], w["traced"]
    n = len(due)
    answered = np.isfinite(done)
    run.counters.update(mean_occupancy=w["occupancy"], calls=worker.calls,
                        due=due + w["t0"], call_of=call_of)
    queries = qnp[w["qidx"]]
    if run.tracer.enabled and traced[0] is not None:
        run.counters["fresh_by_call"] = _recount_fresh(
            worker, queries, call_of, traced, tr["ef"], eng.cfg)
    numbers = check.answers(x, queries[answered], w["ids"][answered],
                            w["dists"][answered], tr["k"])
    numbers["unanswered"] = int(n - answered.sum())
    # how late the replay loop submitted requests: the engine is
    # synchronous, so a request due during a batch waits for its end
    late = {"generator_late_ms_max": float(w["late"].max() * 1e3),
            "generator_late_ms_mean": float(w["late"].mean() * 1e3)}
    return dict(metrics={"query_p95_ms": _p95((done - due) * 1e3)},
                attempted=n, failed=int(n - answered.sum()), numbers=numbers,
                extra=late)


def _recount_fresh(worker, queries, call_of, traced, ef, ecfg):
    """Count, after the window, the program's `n_expanded` of each traced
    engine call: the same search over the same real queries, padded to
    the same bucket (the beam of one query does not depend on another's),
    through `core.search.search` as `StaticWorker` calls it."""
    from repro.core.search import search
    w = worker.worker
    out = {}
    for c in range(*traced):
        q = queries[call_of == c]
        pad = worker.calls[c][2] - len(q)
        qp = np.concatenate([q, np.repeat(q[-1:], pad, axis=0)])
        res = search(w.x, w.graph_ids, qp, k=min(ecfg.k_cap, ef), ef=ef,
                     entry=w.entry, visited=w.visited, overfetch=1)
        out[c] = (int(np.asarray(res.n_expanded)[:len(q)].sum()), len(q))
    return out


def _p95(values) -> float:
    """Nearest-rank 95th percentile; an unanswered request is infinite."""
    xs = np.sort(np.asarray(values, np.float64))
    return float(xs[max(0, int(np.ceil(0.95 * len(xs))) - 1)])


# ------------------------------------------------------------ closed loop


def closed_loop(run) -> dict:
    from repro.core.search import medoid, search

    cfg, tr = run.config, run.traffic
    k, ef, bq = tr["k"], tr["ef"], tr["batch"]
    x, qs = data.corpus(cfg, run.seed, bq * tr["batches"])
    graph = _built_graph(run, x)
    entry = jax.block_until_ready(medoid(x))
    batches = [qs[j * bq:(j + 1) * bq] for j in range(tr["batches"])]

    def call(q):
        res = search(x, graph, q, k=k, ef=ef, entry=entry,
                     visited=tr["visited"])
        return (np.asarray(res.ids), np.asarray(res.dists),
                np.asarray(res.n_expanded))

    call(batches[0])                      # the one shape the window uses
    log(run, "search program warm")
    t0 = run.begin_window()
    run.tracer.start()
    calls, outs = [], []
    while not calls or clock() - t0 < run.seconds:
        with run.span("search"):
            s = clock()
            outs.append(call(batches[len(calls) % len(batches)]))
            calls.append([s, clock()])
    run.tracer.stop()
    run.end_window()

    log(run, f"window: {len(calls)} calls")
    nexp = [o[2] for o in outs]
    run.counters.update(calls=calls, n_expanded=nexp)
    qall = np.concatenate([np.asarray(batches[j % len(batches)])
                           for j in range(len(calls))])
    numbers = check.answers(x, qall, np.concatenate([o[0] for o in outs]),
                            np.concatenate([o[1] for o in outs]), k)
    total = len(calls) * bq
    return dict(metrics={"query_qps": total / (calls[-1][1] - t0)},
                attempted=total, failed=0, numbers=numbers,
                extra={"call_s": [e - s for s, e in calls]})


RUNNERS = {"build": build, "open_loop": open_loop, "closed_loop": closed_loop}
