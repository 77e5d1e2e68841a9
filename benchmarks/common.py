"""Shared benchmark helpers: datasets, timing, CSV rows.

All benchmarks run on XLA:CPU at reduced scale (this container), with the
same code paths the TPU target uses (kernels dispatch per
repro.kernels.ops.get_backend()).  Construction time is wall-clock of the
jitted build, recall measured with the unified search (paper Fig 5/6
protocol: same search algorithm for every index).
"""
from __future__ import annotations

import contextlib
import time
import zlib

import jax

from repro.core import grnnd, recall as R
from repro.core.search import search
from repro.data import synthetic
from repro.kernels import ops

K = 10
EF = 48

# interpret mode steps the kernel grid from Python: benchmarks cap their
# dataset so a full run stays tractable (parity with the fast path is
# separately asserted by the test tier)
INTERPRET_MAX_N = 512


def backend_scope(backend: str | None):
    """Fresh scoped override of the kernel backend; no-op for None."""
    return contextlib.nullcontext() if backend is None else ops.backend(backend)


def resolve_backend(backend: str | None) -> tuple[str, str]:
    """Map a --backend flag to (effective backend, row-name tag).

    The effective backend is what will actually execute ("pallas" raises
    off-TPU rather than run anything else); the tag is the `-<effective>` row-name suffix
    the fig benchmarks append.  The ambient selection (no flag) stays
    untagged EXCEPT when it resolves to interpret: interpret runs shrink
    the benchmark scale, and rows from a shrunken run must never share a
    name with full-scale rows (cross-run comparability, same class of bug
    as the bench_datasets seeding fix).
    """
    with backend_scope(backend):
        eff = ops.effective_backend()
    return eff, f"-{eff}" if (backend is not None or eff == "interpret") else ""


def bench_datasets(n: int = 6000, nq: int = 300):
    """Reduced-scale stand-ins for SIFT1M/DEEP1M/GIST1M."""
    out = {}
    for name, preset in (("sift-like", "sift-like"),
                         ("deep-like", "deep-like"),
                         ("gist-like", "gist-like")):
        # gist floor never exceeds the caller's n: interpret-mode callers
        # clamp n to INTERPRET_MAX_N, and the floor must not bypass that
        nn = n if preset != "gist-like" else min(max(n // 2, 1000), n)
        # crc32, not hash(): str hashing is salted per process, which made
        # every benchmark invocation draw a DIFFERENT dataset — rows from
        # separate runs (e.g. dense vs hashed search) were incomparable
        seed = zlib.crc32(name.encode()) % 2**31
        x = synthetic.make_preset(jax.random.PRNGKey(seed), preset, nn)
        q = synthetic.queries_from(jax.random.PRNGKey(7), x, nq)
        gt = R.brute_force_knn(x, q, K)
        out[name] = (x, q, gt)
    return out


def timed_build(x, cfg: grnnd.GRNNDConfig, key=None, repeats: int = 1):
    """Compile-excluded wall time of the jitted GRNND build."""
    key = key if key is not None else jax.random.PRNGKey(1)
    pool = grnnd.build_graph(key, x, cfg)          # compile + warm
    pool.ids.block_until_ready()
    times = []
    for i in range(repeats):
        t0 = time.perf_counter()
        pool = grnnd.build_graph(jax.random.fold_in(key, i), x, cfg)
        pool.ids.block_until_ready()
        times.append(time.perf_counter() - t0)
    return pool, min(times)


def eval_recall(x, graph_ids, q, gt, ef: int = EF):
    res = search(x, graph_ids, q, k=K, ef=ef)
    return R.recall_at_k(res.ids, gt)


def timed_search(x, graph_ids, q, ef: int = EF, repeats: int = 3,
                 backend: str | None = None, visited: str = "dense",
                 visited_cap: int | None = None, rescore=None,
                 labels=None, filter=None, entry=None, ids_map=None):
    """Compile-excluded search wall time -> (result, QPS).

    `backend`/`visited`/`visited_cap` select the query-path configuration
    (kernels/search_expand.py + hashed visited set); defaults reproduce the
    ambient-backend dense-bitmask search.  `x` may be a VectorStore and
    `rescore` the fp32 tier (the precision ladder, DESIGN.md §8);
    `labels`/`filter` the filtered-search predicate (DESIGN.md §9);
    `entry`/`ids_map` the optimized layout's mapped entry point and
    inverse permutation (core/layout.py, DESIGN.md §10).
    """
    kw = dict(k=K, ef=ef, visited=visited, visited_cap=visited_cap,
              rescore=rescore, labels=labels, filter=filter,
              entry=entry, ids_map=ids_map)
    with backend_scope(backend):
        res = search(x, graph_ids, q, **kw)        # compile + warm
        res.ids.block_until_ready()
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = search(x, graph_ids, q, **kw)
            res.ids.block_until_ready()
            times.append(time.perf_counter() - t0)
    qps = q.shape[0] / min(times)
    return res, qps


def row(name: str, seconds: float, derived: str, *,
        precision: str = "fp32", bytes_per_vector: float = 0.0,
        opt_layout: str | None = None) -> str:
    """One harness CSV row.

    Every row carries the traversal-tier `precision=` and `bpv=` (bytes
    per stored vector; 0.0 where no vector storage is involved, e.g.
    analytic cells) so the perf trajectory can distinguish dtype
    regressions from algorithmic ones — benchmarks/run.py validates both
    fields on the smoke artifact (SMOKE_SCHEMA 2).  `opt_layout` is the
    graph-layout tag (SMOKE_SCHEMA 4, core/layout.py): "none" for the raw
    pool layout, or the ordering (+ pruned degree) of an optimized index —
    required on every fig6 row so the QPS trajectory never silently mixes
    layouts.
    """
    opt = "" if opt_layout is None else f" opt_layout={opt_layout}"
    return (f"{name},{seconds * 1e6:.1f},{derived}"
            f" precision={precision} bpv={bytes_per_vector:.1f}{opt}")


def fp32_bpv(x) -> float:
    """Traversal-tier bytes/vector of a plain fp32 dataset."""
    return 4.0 * x.shape[1]
