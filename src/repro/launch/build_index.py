"""Build a GRNND index over a vector dataset and save it.

    PYTHONPATH=src python -m repro.launch.build_index --dataset sift-small \
        --out /tmp/sift.idx.npz [--sharded]

--sharded uses the multi-device build (requires >1 jax device or forced
host devices).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.grnnd_paper import DATASETS
from repro.core import build_graph, sharded_build_graph
from repro.data import synthetic
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="sift-small",
                    choices=sorted(DATASETS))
    ap.add_argument("--out", required=True)
    ap.add_argument("--sharded", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    ds = DATASETS[args.dataset]
    preset = {"sift": "sift-like", "deep": "deep-like",
              "gist": "gist-like"}[args.dataset.split("-")[0]]
    x = synthetic.make_preset(jax.random.PRNGKey(args.seed), preset, ds.n)

    t0 = time.perf_counter()
    if args.sharded:
        devs = len(jax.devices())
        mesh = make_mesh((devs,), ("data",))
        pool = sharded_build_graph(mesh, ("data",),
                                   jax.random.PRNGKey(args.seed + 1), x,
                                   ds.build)
    else:
        pool = build_graph(jax.random.PRNGKey(args.seed + 1), x, ds.build)
    pool.ids.block_until_ready()
    dt = time.perf_counter() - t0

    np.savez_compressed(args.out, ids=np.asarray(pool.ids),
                        dists=np.asarray(pool.dists), x=np.asarray(x))
    print(f"built {args.dataset} (n={ds.n}, d={ds.d}) in {dt:.1f}s "
          f"-> {args.out}")


if __name__ == "__main__":
    main()
