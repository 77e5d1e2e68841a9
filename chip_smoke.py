"""Chip smoke run: the GRNND build and query path on a TPU, end to end.

    python chip_smoke.py                # one chip, SIFT1M shapes
    python chip_smoke.py --chips 4      # the sharded paths on four chips

One chip: on a sift-like synthetic corpus made from --seed at SIFT1M's
shapes and build config (configs/grnnd_paper.py; the "sift1m-like"
preset of data/synthetic.py, whose clusters have SIFT's local intrinsic
dimension), it

  1. checks every main-path kernel variant against its ref.py oracle on
     the chip, at a small size;
  2. builds the graph with `core.build_graph` (what launch/build_index.py
     calls) and answers 1,000 held-out queries with `core.search.search`;
  3. serves a few hundred requests through `serve.ann_engine.AnnEngine`
     with a `StaticWorker` (what `launch/serve.py --engine` runs);

and checks recall@10 at ef=128 against brute force under the plain-jnp
`ref` backend, the ref-backend search on the same graph against the
Pallas one, and the engine's results against direct `search()` calls.

Four chips: `distributed_search` (1,002 queries, not a multiple of 4)
and corpus-sharded search at S=4, each against single-device `search()`
on chip 0 (ids must be equal), and `sharded_build_graph` on the 4-chip
mesh against the one-chip build (recall@10 within 0.02).

Earlier lines report phase times (compile apart from run), the device,
the effective kernel backend and peak device memory.  The last line is
one JSON object.  Any failed check, a platform other than TPU, or a
kernel backend other than "pallas" exits non-zero without it.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

K, EF = 10, 128
N_QUERIES = 1000
N_REQUESTS = 300


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        log(f"check {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip())
        if not ok:
            self.failed.append(name)


def timed(name: str, fn, *args):
    """Run fn(*args) to completion; print and return (result, seconds)."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    dt = time.perf_counter() - t0
    log(f"phase {name}: {dt:.3f} s")
    return out, dt


def aot(name: str, fn, *args, static_argnums=()):
    """Compile fn for args (timed on its own); return the executable.

    A fresh partial per call: jax.jit caches traces by function, and the
    kernel backend is picked at trace time.
    """
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(functools.partial(fn), static_argnums=static_argnums
                       ).lower(*args).compile()
    log(f"phase {name} compile: {time.perf_counter() - t0:.3f} s")
    return compiled


def kernel_parity(check: Checks) -> None:
    """Every main-path kernel variant on the chip vs its oracle, small size.

    Each call goes through the `ops` dispatch layer twice: under the
    selected backend and under `ref`.  ids, masks and merges must be
    equal; distances agree to fp32 rounding (Mosaic's reduction tree over
    D may differ from XLA's).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import labels as L
    from repro.core import vecstore as VS
    from repro.data import synthetic
    from repro.kernels import ops

    n, d, r, p, c, q = 4096, 128, 48, 48, 300, 100
    ks = jax.random.split(jax.random.PRNGKey(7), 12)
    x = synthetic.make_preset(ks[0], "sift-like", n)
    ids = jax.random.randint(ks[1], (c, r), -1, n)
    dists = jnp.where(ids >= 0, jax.random.uniform(ks[2], (c, r)) * 40.0,
                      jnp.inf)
    si = jax.random.randint(ks[3], (c, p), 0, r)
    sj = jax.random.randint(ks[4], (c, p), 0, r)
    qv = synthetic.queries_from(ks[5], x, q)
    nbrs = jax.random.randint(ks[6], (q, r), -1, n)
    tab = jnp.where(jax.random.bernoulli(ks[7], 0.3, (q, 1024)),
                    jax.random.randint(ks[8], (q, 1024), 0, n), -1)
    valid = jax.random.bernoulli(ks[9], 0.9, (n,))
    vw = L.encode_labels(jax.random.randint(ks[10], (n,), 0, 40), 40).words
    fw = L.random_query_filters(ks[11], q, 40, 0.2)
    ni = jax.random.randint(ks[1], (5000,), 0, n)
    nj = jax.random.randint(ks[2], (5000,), 0, n)
    ti = jax.random.randint(ks[3], (q, EF + r), -1, n // 8)
    td = jnp.round(jax.random.uniform(ks[4], (q, EF + r)) * 50.0)

    def compare(name, fn, float_idx=(), max_flips=0):
        got = fn()
        with ops.backend("ref"):
            want = fn()
        got = got if isinstance(got, tuple) else (got,)
        want = want if isinstance(want, tuple) else (want,)
        flips, worst, ok = 0, 0.0, True
        for i, (g, w) in enumerate(zip(got, want)):
            g, w = np.asarray(g), np.asarray(w)
            if i in float_idx:
                fin = np.isfinite(w)
                ok &= bool(np.array_equal(fin, np.isfinite(g)))
                ok &= bool(np.allclose(g[fin], w[fin], rtol=1e-5, atol=1e-4))
                if fin.any():
                    worst = max(worst, float(np.max(
                        np.abs(g[fin] - w[fin])
                        / np.maximum(np.abs(w[fin]), 1e-6))))
            else:
                flips += int(np.sum(g != w))
        check(f"kernel {name}", ok and flips <= max_flips,
              f"mismatches={flips} max_rel_dist_err={worst:.3e}")

    for prec in VS.PRECISIONS:
        st = VS.encode(x, prec)
        # a pair whose distance sits within rounding of its RNG threshold
        # may flip on the chip; allow a handful of the 14,400 pairs
        compare(f"rng_round[{prec}]",
                lambda: ops.rng_propagation_round(st, ids, dists, si, sj),
                float_idx=(2,), max_flips=4)
    for prec in ("fp32", "int8"):
        st = VS.encode(x, prec)
        compare(f"search_expand[{prec},hashed]",
                lambda: ops.search_expand(st, qv, nbrs, tab), float_idx=(1,))
        compare(f"gather_l2[{prec}]", lambda: ops.gather_sqdist(st, ni, nj),
                float_idx=(0,))
    compare("search_expand[fp32,dense,valid,filtered]",
            lambda: ops.search_expand(x, qv, nbrs, tab[:, :1], valid, vw, fw),
            float_idx=(1,))
    compare("topr_merge", lambda: ops.topr_merge(ti, td, EF))
    compare("pairwise_l2", lambda: ops.pairwise_sqdist(qv, x),
            float_idx=(0,))
    compare("rowwise_l2", lambda: ops.rowwise_sqdist(qv, x[:q]),
            float_idx=(0,))


def sift_like(seed: int, n: int):
    """Base set of n points and N_QUERIES held-out queries, on device."""
    import jax

    from repro.data import synthetic
    allx = synthetic.make_preset(jax.random.PRNGKey(seed), "sift1m-like",
                                 n + N_QUERIES)
    return allx[:n], allx[n:]


def ground_truth(x, q):
    from repro.core.recall import brute_force_knn
    from repro.kernels import ops
    with ops.backend("ref"):
        return timed("ground truth (ref brute force)",
                     lambda: brute_force_knn(x, q, K, chunk=128))[0]


def one_chip(args, check: Checks) -> None:
    import jax
    import numpy as np

    from repro.configs.grnnd_paper import SIFT1M
    from repro.core import build_graph
    from repro.core.recall import recall_at_k
    from repro.core.search import search
    from repro.kernels import ops
    from repro.serve.ann_engine import AnnEngine, EngineConfig, StaticWorker

    kernel_parity(check)
    cfg = SIFT1M.build
    if args.n != SIFT1M.n:
        log(f"cut: n={args.n} (SIFT1M n={SIFT1M.n}); d, r and the build "
            "config unchanged")
    log(f"config: n={args.n} d={SIFT1M.d} {cfg}")
    (x, q), _ = timed("data", sift_like, args.seed, args.n)

    key = jax.random.PRNGKey(args.seed + 1)
    build = aot("build", build_graph, key, x, cfg, static_argnums=2)
    pool, t_build = timed("build run", build, key, x)
    log(f"build: {args.n / t_build:.1f} vectors/s (one run, compile "
        "excluded)")
    ids = pool.ids

    def srch(xx, g, qq):
        return search(xx, g, qq, k=K, ef=EF)

    run = aot("search", srch, x, ids, q)
    res, _ = timed("search run", run, x, ids, q)
    gt = ground_truth(x, q)
    rec = recall_at_k(res.ids, gt)
    check(f"recall@{K} ef={EF} >= 0.85", rec >= 0.85, f"recall={rec:.4f}")

    with ops.backend("ref"):
        run_ref = aot("search[ref]", srch, x, ids, q)
    res_ref, _ = timed("search[ref] run", run_ref, x, ids, q)
    rec_ref = recall_at_k(res_ref.ids, gt)
    check("ref-backend recall within 0.01 of pallas",
          abs(rec_ref - rec) <= 0.01,
          f"recall_ref={rec_ref:.4f} recall_pallas={rec:.4f}")

    eng = AnnEngine(StaticWorker(x, ids), EngineConfig())
    qr = np.asarray(q[:N_REQUESTS])

    def serve():
        rids = [eng.submit(v, k=K, ef=EF) for v in qr]
        eng.run()
        return [eng.take_result(rid) for rid in rids]

    results, _ = timed(f"engine ({N_REQUESTS} requests, compile included)",
                       serve)
    direct = search(x, ids, q[:N_REQUESTS], k=K, ef=EF)
    got_i = np.stack([r.ids for r in results])
    got_d = np.stack([r.dists for r in results])
    check("engine results equal direct search()",
          np.array_equal(got_i, np.asarray(direct.ids))
          and np.array_equal(got_d, np.asarray(direct.dists)),
          f"id_mismatches={int(np.sum(got_i != np.asarray(direct.ids)))}")


def four_chips(args, check: Checks) -> None:
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from repro.configs.grnnd_paper import SIFT1M
    from repro.core import build_graph, corpus_shard as CS
    from repro.core.distributed import distributed_search, sharded_build_graph
    from repro.core.recall import recall_at_k
    from repro.core.search import search
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    if len(devs) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    mesh = make_mesh((4,), ("data",))
    cfg = SIFT1M.build
    log(f"config: n={args.n} d={SIFT1M.d} {cfg} mesh={dict(mesh.shape)}")
    chip0 = SingleDeviceSharding(devs[0])
    (x, q), _ = timed("data", sift_like, args.seed, args.n)
    x, q = jax.device_put(x, chip0), jax.device_put(q, chip0)
    q2 = jax.numpy.concatenate([q, q[:2]])        # 1,002 queries
    key = jax.random.PRNGKey(args.seed + 1)

    pool, _ = timed("one-chip build (chip 0)", build_graph, key, x, cfg)
    gt = ground_truth(x, q)
    single, _ = timed("one-chip search (chip 0)",
                      lambda: search(x, pool.ids, q2, k=K, ef=EF))
    rec1 = recall_at_k(single.ids[:N_QUERIES], gt)
    log(f"one-chip recall@{K}={rec1:.4f}")

    def devset(name, a):
        log(f"sharding {name}: device_set size {len(a.sharding.device_set)}")

    spool, _ = timed("sharded_build_graph (4 chips)", sharded_build_graph,
                     mesh, ("data",), key, x, cfg)
    devset("sharded pool ids", spool.ids)
    devset("sharded pool dists", spool.dists)
    gids = jax.device_put(spool.ids, chip0)
    res4 = search(x, gids, q, k=K, ef=EF)
    rec4 = recall_at_k(res4.ids, gt)
    check("sharded build recall within 0.02 of one-chip build",
          abs(rec4 - rec1) <= 0.02,
          f"recall_sharded={rec4:.4f} recall_one_chip={rec1:.4f}")

    dist, _ = timed("distributed_search (4 chips, 1002 queries)",
                    lambda: distributed_search(mesh, ("data",), x, pool.ids,
                                               q2, k=K, ef=EF))
    devset("distributed_search ids", dist.ids)
    check("distributed_search ids equal single-device search",
          np.array_equal(np.asarray(dist.ids), np.asarray(single.ids)),
          f"mismatches={int(np.sum(np.asarray(dist.ids) != np.asarray(single.ids)))}")

    idx = CS.shard(x, pool.ids, 4)
    cs, _ = timed("corpus-sharded search (S=4)",
                  lambda: idx.search(q2, k=K, ef=EF, mesh=mesh))
    devset("corpus-sharded ids", cs.ids)
    check("corpus-sharded ids equal single-device search",
          np.array_equal(np.asarray(cs.ids), np.asarray(single.ids)),
          f"mismatches={int(np.sum(np.asarray(cs.ids) != np.asarray(single.ids)))}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=None,
                    help="corpus size (default: SIFT1M's 1,000,000); a cut "
                         "keeps d, r and the build config")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax

    from repro.configs.grnnd_paper import SIFT1M
    from repro.kernels import ops

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    backend = ops.effective_backend()
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())}  backend={backend}  cache={cache}")
    if backend != "pallas":
        print(f"chip_smoke: kernel backend {backend!r}, not 'pallas'",
              file=sys.stderr)
        return 2
    args.n = args.n or SIFT1M.n

    check = Checks()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args, check)
    log(f"phase total: {time.perf_counter() - t0:.3f} s")
    for i, d in enumerate(jax.devices()[:args.chips]):
        stats = d.memory_stats() or {}
        log(f"device {i} peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    if check.failed:
        print(f"chip_smoke: failed checks: {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
