"""Build quality of one GRNND build on the chip smoke's data.

    python benchmarks/graph_quality.py --n 200000 --backend ref
    python benchmarks/graph_quality.py --n 200000 --chunk none --save g.npz

Builds the SIFT1M config (configs/grnnd_paper.py; --chunk overrides its
chunk_size) on the corpus `chip_smoke.py` makes from --seed (--preset,
default "sift1m-like"), with the same key, and reports:

  * graph recall@10: for a sample of vertices, the fraction of each
    one's exact 10 nearest neighbours (self excluded, brute force) found
    anywhere in its R-slot pool;
  * search recall@10 of `core.search.search` at each --ef over the 1,000
    held-out queries, against brute force.

It uses only `core.build_graph`, `core.search.search`, `core.recall` and
`kernels.ops.backend`, so it runs unchanged against older checkouts of
this repository (copy it into their `benchmarks/`, with --preset
sift-like where they predate "sift1m-like"); --save writes the
built pool ids so two builds can be compared bit for bit.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.grnnd_paper import SIFT1M  # noqa: E402
from repro.core import build_graph  # noqa: E402
from repro.core.recall import brute_force_knn, recall_at_k  # noqa: E402
from repro.core.search import search  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.kernels import ops  # noqa: E402

K, N_QUERIES = 10, 1000


def graph_recall(x, ids, sample: int, seed: int) -> float:
    """Mean fraction of a sampled vertex's exact 10-NN present in its pool."""
    n = x.shape[0]
    rows = np.random.default_rng(seed).choice(n, size=sample, replace=False)
    with ops.backend("ref"):
        # k+1 nearest include the vertex itself at distance 0
        nn = np.asarray(brute_force_knn(x, x[rows], K + 1, chunk=128))
    pool = np.asarray(ids)[rows]
    hits = 0
    for v, true, have in zip(rows, nn, pool):
        true = [t for t in true.tolist() if t != v][:K]
        hits += len(set(true) & set(have.tolist()))
    return hits / (sample * K)


def report(args, cfg) -> None:
    """Build once and print the build's graph and search recall."""
    print(f"n={args.n} preset={args.preset} seed={args.seed} "
          f"backend={ops.effective_backend()} {cfg}", flush=True)

    allx = synthetic.make_preset(jax.random.PRNGKey(args.seed), args.preset,
                                 args.n + N_QUERIES)
    x, q = allx[:args.n], allx[args.n:]
    t0 = time.perf_counter()
    pool = jax.block_until_ready(
        build_graph(jax.random.PRNGKey(args.seed + 1), x, cfg))
    print(f"build: {time.perf_counter() - t0:.3f} s (compile included)",
          flush=True)
    if args.save:
        np.savez(args.save, ids=np.asarray(pool.ids))
    print(f"graph recall@{K} ({args.sample} vertices): "
          f"{graph_recall(x, pool.ids, args.sample, args.seed):.4f}",
          flush=True)

    with ops.backend("ref"):
        gt = brute_force_knn(x, q, K, chunk=128)
    for ef in args.ef:
        res = search(x, pool.ids, q, k=K, ef=ef, max_steps=args.max_steps)
        print(f"search recall@{K} ef={ef} max_steps={args.max_steps}: "
              f"{recall_at_k(res.ids, gt):.4f}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--preset", default="sift1m-like",
                    help="data/synthetic.py preset of the corpus")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", default="config",
                    help="build chunk size: an int, 'none' (one shot), or "
                         "'config' (SIFT1M's)")
    ap.add_argument("--backend", default=None,
                    help="kernel backend by name (ref, interpret, pallas)")
    ap.add_argument("--ef", type=int, nargs="+", default=[128])
    ap.add_argument("--max-steps", type=int, default=512,
                    help="search step cap (search()'s default)")
    ap.add_argument("--sample", type=int, default=1000,
                    help="vertices sampled for graph recall")
    ap.add_argument("--save", default=None, help="write pool ids to .npz")
    args = ap.parse_args()

    cfg = SIFT1M.build
    if args.chunk != "config":
        cfg = cfg._replace(
            chunk_size=None if args.chunk == "none" else int(args.chunk))
    with (ops.backend(args.backend) if args.backend
          else contextlib.nullcontext()):
        report(args, cfg)
    return 0


if __name__ == "__main__":
    sys.exit(main())
