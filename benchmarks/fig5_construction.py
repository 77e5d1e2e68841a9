"""Fig 5 analogue: construction time at matched recall — GRNND vs the
sequential CPU RNN-Descent baseline (and random init as a floor).

The paper's protocol: fixed search algorithm + search params; each method
tunes construction only.  Derived column: recall@10 and speedup over the
sequential baseline.

Backend selection (the fused propagation-round kernel):

    PYTHONPATH=src python benchmarks/fig5_construction.py --backend pallas

records the fused-kernel construction path; it needs a TPU.  Off-TPU,
`--backend interpret` runs the same kernels Python-stepped, which is a
CORRECTNESS harness, not a performance mode — the benchmark shrinks the
dataset so the end-to-end run stays tractable, and the row is labeled
with the effective backend.  The numbers that matter for the fused path on real
hardware come from the analytic roofline (benchmarks/roofline.py) and
from a TPU run of this same flag.  See EXPERIMENTS.md §Perf cell F.
"""
from __future__ import annotations

import argparse
import time

if __package__ in (None, ""):  # direct `python benchmarks/fig5_construction.py`
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import numpy as np
import jax.numpy as jnp

from benchmarks import common as C
from repro.core import grnnd, rnnd_ref, pools


def run(n_seq: int = 2500, backend: str | None = None) -> list[str]:
    """`backend` applies to the GRNND BUILD only (the system under test);
    ground truth and recall evaluation keep the fixed default search path,
    per the paper's protocol."""
    eff, tag = C.resolve_backend(backend)
    if eff == "interpret":
        n_seq = min(n_seq, C.INTERPRET_MAX_N)

    rows = []
    for name, (x, q, gt) in C.bench_datasets(n=n_seq).items():
        n = x.shape[0]
        # --- sequential RNN-Descent (paper's CPU baseline) ---
        xs = np.asarray(x)
        t0 = time.perf_counter()
        adj = rnnd_ref.build_graph_ref(xs, s=12, r=24, t1=2, t2=2, seed=0)
        t_seq = time.perf_counter() - t0
        ids_seq = jnp.asarray(rnnd_ref.adjacency_to_pool_arrays(adj, 24))
        r_seq = C.eval_recall(x, ids_seq, q, gt)
        rows.append(C.row(f"fig5/{name}/rnnd-cpu", t_seq,
                          f"recall={r_seq:.3f} speedup=1.0x",
                          bytes_per_vector=C.fp32_bpv(x)))

        # --- GRNND (parallel, disordered; fused round per backend) ---
        # NOTE on this CPU-only container: wall-clock measures TOTAL work
        # on one core; the paper's GPU speedup comes from parallelism.  The
        # architecture-independent metric is the dependency critical path:
        # sequential RNN-Descent = N*T1*T2 ordered vertex updates, GRNND =
        # T1*T2 rounds of fully independent vertex updates.
        cfg = grnnd.GRNNDConfig(s=12, r=24, t1=3, t2=4, rho=0.6,
                                pairs_per_vertex=24)
        with C.backend_scope(backend):
            pool, t_g = C.timed_build(x, cfg)
        r_g = C.eval_recall(x, pool.ids, q, gt)
        path_seq = n * 2 * 2
        path_g = cfg.t1 * cfg.t2
        rows.append(C.row(
            f"fig5/{name}/grnnd{tag}", t_g,
            f"recall={r_g:.3f} cpu1core_speedup={t_seq / t_g:.2f}x "
            f"backend={eff} "
            f"critical_path={path_g} vs_seq={path_seq} "
            f"parallel_depth_ratio={path_seq / path_g:.0f}x",
            bytes_per_vector=C.fp32_bpv(x)))

        # --- random S-NN init (quality floor) ---
        p0 = pools.init_random(jax.random.PRNGKey(2), x, 12, 24)
        r_0 = C.eval_recall(x, p0.ids, q, gt)
        rows.append(C.row(f"fig5/{name}/random-init", 0.0,
                          f"recall={r_0:.3f} speedup=inf",
                          bytes_per_vector=C.fp32_bpv(x)))
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default=None,
                    choices=["auto", "pallas", "interpret", "ref", "xla"],
                    help="kernel backend for the GRNND build "
                         "(default: current REPRO_KERNEL_BACKEND/auto)")
    ap.add_argument("--n", type=int, default=2500,
                    help="vectors per dataset (interpret runs are capped "
                         f"at {C.INTERPRET_MAX_N})")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for row in run(n_seq=args.n, backend=args.backend):
        print(row, flush=True)
