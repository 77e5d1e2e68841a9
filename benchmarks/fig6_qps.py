"""Fig 6 analogue: QPS vs recall of the constructed indices.

Fixed construction settings per method; the search parameter (ef) sweeps the
QPS-recall curve with the SAME unified search for every graph.

Query-path configuration (EXPERIMENTS.md §Perf cell E):

    PYTHONPATH=src python benchmarks/fig6_qps.py --backend pallas \
        --visited hashed

`--backend` selects the kernel path of the SEARCH (the fused
`search_expand` kernel; "pallas" needs a TPU, and off-TPU "interpret"
runs the same kernels as a correctness harness, so the dataset is capped
and rows are labeled with the effective backend).  `--visited` selects the visited-set
representation (dense (Q, N) bitmask vs the O(Q·H) hashed table).  Graph
construction stays on the ambient default path: the graph under test is
identical across query configurations, per the paper's protocol.

`--optimize-layout` adds before/after rows for the post-build layout pass
(core/layout.py, DESIGN.md §10): next to every baseline `grnnd` row, a
`grnnd-opt` row searches the SAME graph after BFS renumbering + detour
pruning to half the pool width — the QPS side of the layout trade (the
bitwise-exact unpruned configuration is covered by tests/test_layout.py;
this row quantifies the speed a caller buys by opting into pruning).
Every fig6 row carries an `opt_layout=` tag (SMOKE_SCHEMA 4) and the
smoke gate requires QPS(optimized) >= QPS(baseline) per (dataset, ef).
"""
from __future__ import annotations

import argparse
import re

if __package__ in (None, ""):  # direct `python benchmarks/fig6_qps.py`
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.core import grnnd, layout, rnnd_ref
from repro.core.recall import recall_at_k


def run(n: int = 4000, backend: str | None = None, visited: str = "dense",
        visited_cap: int | None = None,
        optimize_layout: bool = False) -> list[str]:
    eff, tag = C.resolve_backend(backend)
    # interpret mode steps the (Q, R) kernel grid from Python once per beam
    # step: shrink vectors/queries/sweep so the end-to-end run stays in
    # minutes (parity with the fast path is asserted by the test tier)
    interp = eff == "interpret"
    nq, repeats, efs = (64, 1, (16, 32)) if interp else (300, 2, (16, 32, 64, 128))
    if interp:
        n = min(n, C.INTERPRET_MAX_N)
    # encode the full query-path configuration in the row name so rows from
    # different runs are never incomparable under the same label
    vtag = "" if visited == "dense" else f"-{visited}"
    if visited == "hashed" and visited_cap is not None:
        vtag += f"-c{visited_cap}"

    rows = []
    for name, (x, q, gt) in C.bench_datasets(n=n, nq=nq).items():
        cfg = grnnd.GRNNDConfig(s=12, r=24, t1=3, t2=4, rho=0.6,
                                pairs_per_vertex=24)
        pool, _ = C.timed_build(x, cfg)

        opt = opt_tag = None
        if optimize_layout:
            # the QPS-side configuration: BFS renumbering + detour pruning
            # to half the pool width — halves the per-step row-DMA work,
            # which is what the QPS(opt) >= QPS(base) smoke gate measures.
            # (The bitwise-exact unpruned layout is the test tier's job.)
            opt = layout.optimize(x, pool, order="bfs", prune=True,
                                  degree=max(4, cfg.r // 2))
            opt_tag = f"bfs-p{opt.degree}"

        ids_seq = None
        if x.shape[0] <= 3000 and not interp:  # sequential baseline, small n
            adj = rnnd_ref.build_graph_ref(np.asarray(x), s=12, r=24,
                                           t1=2, t2=2, seed=0)
            ids_seq = jnp.asarray(rnnd_ref.adjacency_to_pool_arrays(adj, 24))

        for ef in efs:
            res, qps = C.timed_search(x, pool.ids, q, ef=ef, repeats=repeats,
                                      backend=backend, visited=visited,
                                      visited_cap=visited_cap)
            rec = recall_at_k(res.ids, gt)
            rows.append(C.row(f"fig6/{name}/grnnd{tag}{vtag}/ef{ef}",
                              1.0 / qps, f"recall={rec:.3f} qps={qps:.0f}",
                              bytes_per_vector=C.fp32_bpv(x),
                              opt_layout="none"))
            if opt is not None:
                res_o, qps_o = C.timed_search(
                    opt.x, opt.graph_ids, q, ef=ef, repeats=repeats,
                    backend=backend, visited=visited,
                    visited_cap=visited_cap, entry=opt.entry,
                    ids_map=opt.inv)
                rec_o = recall_at_k(res_o.ids, gt)
                rows.append(C.row(
                    f"fig6/{name}/grnnd-opt{tag}{vtag}/ef{ef}", 1.0 / qps_o,
                    f"recall={rec_o:.3f} qps={qps_o:.0f}",
                    bytes_per_vector=C.fp32_bpv(x), opt_layout=opt_tag))
            if ids_seq is not None:
                res2, qps2 = C.timed_search(x, ids_seq, q, ef=ef,
                                            repeats=repeats, backend=backend,
                                            visited=visited,
                                            visited_cap=visited_cap)
                rec2 = recall_at_k(res2.ids, gt)
                rows.append(C.row(f"fig6/{name}/rnnd-cpu{tag}{vtag}/ef{ef}",
                                  1.0 / qps2,
                                  f"recall={rec2:.3f} qps={qps2:.0f}",
                                  bytes_per_vector=C.fp32_bpv(x),
                                  opt_layout="none"))
    return rows


_QPS_RE = re.compile(r"(?:^|\s)qps=(\S+)")


def validate_layout_rows(parsed: list[dict]) -> None:
    """SMOKE_SCHEMA 4 gate (benchmarks/run.py): every fig6 row carries an
    `opt_layout=` tag, and every optimized row beats (or ties) its baseline
    partner's QPS — "optimized index => identical results, higher QPS" is
    the whole point of the layout pass, so a regression here fails the
    build instead of silently landing in the trajectory."""
    fig6 = [p for p in parsed if p["name"].startswith("fig6/")]
    by_name = {}
    for p in fig6:
        if not p.get("opt_layout"):
            raise ValueError(f"fig6 row lacks an opt_layout= tag: "
                             f"{p['name']!r}")
        m = _QPS_RE.search(p["derived"])
        if not m:
            raise ValueError(f"fig6 row lacks a qps= field: {p['name']!r}")
        by_name[p["name"]] = float(m.group(1))
    opt_rows = [p for p in fig6 if p["opt_layout"] != "none"]
    if not any(p["opt_layout"] == "none" for p in fig6):
        raise ValueError("fig6 has no baseline (opt_layout=none) rows")
    for p in opt_rows:
        base_name = p["name"].replace("/grnnd-opt", "/grnnd", 1)
        if base_name == p["name"] or base_name not in by_name:
            raise ValueError(f"optimized fig6 row {p['name']!r} has no "
                             f"baseline partner {base_name!r}")
        q_opt, q_base = by_name[p["name"]], by_name[base_name]
        if q_opt < q_base:
            raise ValueError(
                f"layout regression: QPS(optimized)={q_opt:.0f} < "
                f"QPS(baseline)={q_base:.0f} for {p['name']!r}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default=None,
                    choices=["auto", "pallas", "interpret", "ref", "xla"],
                    help="kernel backend for the SEARCH (default: current "
                         "REPRO_KERNEL_BACKEND/auto)")
    ap.add_argument("--visited", default="dense",
                    choices=["dense", "hashed"],
                    help="visited-set representation of the search")
    ap.add_argument("--visited-cap", type=int, default=None,
                    help="hashed-table slots per query "
                         "(default: core.search.default_visited_cap(ef))")
    ap.add_argument("--n", type=int, default=4000,
                    help="vectors per dataset (interpret runs are capped "
                         f"at {C.INTERPRET_MAX_N})")
    ap.add_argument("--optimize-layout", action="store_true",
                    help="add before/after rows for the post-build layout "
                         "pass (BFS renumbering + detour pruning to half "
                         "degree, core/layout.py)")
    args = ap.parse_args()
    if args.visited_cap is not None and args.visited != "hashed":
        ap.error("--visited-cap only applies with --visited hashed")
    print("name,us_per_call,derived")
    for row in run(n=args.n, backend=args.backend, visited=args.visited,
                   visited_cap=args.visited_cap,
                   optimize_layout=args.optimize_layout):
        print(row, flush=True)
