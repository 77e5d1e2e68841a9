"""Serve batched ANN queries against a saved GRNND index.

    PYTHONPATH=src python -m repro.launch.serve --index /tmp/sift.idx.npz \
        [--batches 8] [--ef 48] [--backend pallas] [--visited hashed] \
        [--visited-cap 512] [--shards 4] [--precision int8] \
        [--mutable --churn 64] [--filter-labels 100 --selectivity 0.1] \
        [--engine --requests 256 --offered-qps 500 --mix-k 5,10]

`--backend` selects the kernel path of the fused expansion step
(`kernels/search_expand.py`; "pallas" needs a TPU, "interpret" runs the
same kernel bodies in Python anywhere).
`--visited hashed` swaps the dense (Q, N) visited bitmask for the O(Q·H)
per-query open-addressed table — the memory-flat serving configuration
(DESIGN.md §6).  `--shards K` shards the query batch over the first K
devices via `core.distributed.distributed_search` (bitwise-identical to
the single-device search).  On a TPU host K counts its chips (1 or 4 on a
v5e host); on a CPU box JAX sees one device unless host devices are
forced (XLA_FLAGS=--xla_force_host_platform_device_count=K), which is for
tests only.

`--corpus-shards S` shards the CORPUS instead (core/corpus_shard.py,
DESIGN.md §11): each shard owns 1/S of the vectors, graph rows, labels,
and rescore tier — the layout that breaks the single-device memory
ceiling on N.  Results are bitwise-identical to the replicated search for
any S (the tests/test_corpus_shard.py invariance tier).  The shards map
one-per-device over a mesh, so S may not exceed the device count: the
run errors rather than fall back to an in-process executor that would
hold every shard on one device.  Mutually exclusive
with `--shards` (one sharding axis per process; compose them via a 2-D
mesh in a custom launcher) and `--mutable`.

`--precision {fp32,bf16,int8}` selects the traversal-tier storage (the
precision ladder, DESIGN.md §8): bf16 halves and int8 quarters the
bytes/vector the bandwidth-bound expansion kernel reads.  At int8 the
final ef candidates are re-ranked against the fp32 tier (exact
distances) unless `--no-rescore` is given; the printed `bpv=` column is
the traversal-tier bytes/vector.

`--tier {device,host}` places that fp32 rescore tier (DESIGN.md §13):
`host` pins it on the CPU backend — device memory holds the quantized
traversal tier + graph only — and the re-rank gathers the final ef rows
per query across the host boundary.  Results are bitwise-identical to
`--tier device` (tests/test_tiered.py); requires a quantized
`--precision` with rescoring on.  Composes with every serving mode:
`--shards` (the tier never replicates onto the mesh), `--corpus-shards`
(no per-shard rescore slice exists), `--engine`, and `--mutable`
(inserts write the host tier in place).

`--filter-labels L` turns on FILTERED serving (DESIGN.md §9): every vertex
gets a synthetic label uniform in [0, L) (deterministic seed), and each
query carries a random allowed-label predicate of ~`--selectivity`·L
labels.  The search routes through filtered-out vertices but returns only
predicate-passing ids (a hard invariant, printed as `pred_ok=`; recall is
scored against brute force over each query's ALLOWED subset).  `ef` is
automatically raised to the over-fetch floor ~4·k/selectivity (§9.3) —
the printed `ef=` field shows the effective value.  Composes with
`--shards` (predicates shard with the queries) and `--mutable` (labels
ride through insert/delete/compact).

`--engine` replaces the fixed-batch loop with the continuous-batching
engine (`serve/ann_engine.py`, DESIGN.md §12): a synthetic open-loop
trace of small heterogeneous requests — k/ef drawn per request from
`--mix-k`/`--mix-ef`, every other request filtered under
`--filter-labels`, insert/delete churn every `--churn-every` queries
under `--mutable` — is coalesced into jit-bucketed `(Q, ef, filtered?)`
batches.  Results are bitwise-identical to the direct path
(tests/test_ann_engine.py); the report adds p50/p99 per-request latency,
achieved vs offered QPS, batch occupancy, and the compiled-bucket count.
Composes with `--precision`, `--optimize-layout`, `--corpus-shards`, and
`--mutable` (but not `--shards`: the engine shapes its own batches).

`--mutable` wraps the loaded index in a `core.dynamic.DynamicIndex` and
interleaves mutation requests with the query batches: every batch first
INSERTS `--churn` fresh vectors and DELETES the `--churn` oldest live
labels (a sliding-window corpus, the workload a static build cannot
serve), then runs the search batch.  Recall is scored against exact
brute force over the LIVE corpus, and mutation latency is reported next
to query throughput.  Compaction auto-triggers on the tombstone
threshold (DESIGN.md §7).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import brute_force_knn, recall_at_k, vecstore
from repro.core import labels as lab
from repro.core import layout
from repro.core.distributed import distributed_search
from repro.core.dynamic import DynamicConfig, DynamicIndex
from repro.core.pools import Pool
from repro.core.search import medoid, overfetch_ef, search
from repro.data import synthetic
from repro.kernels import ops
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--index", required=True)
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--ef", type=int, default=48)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--backend", default=None,
                    choices=["auto", "pallas", "interpret", "ref", "xla"],
                    help="kernel backend for the search "
                         "(default: current REPRO_KERNEL_BACKEND/auto)")
    ap.add_argument("--visited", default="dense",
                    choices=["dense", "hashed"],
                    help="visited-set representation")
    ap.add_argument("--visited-cap", type=int, default=None,
                    help="hashed-table slots per query "
                         "(default: core.search.default_visited_cap(ef))")
    ap.add_argument("--shards", type=int, default=0,
                    help="shard query batches over this many devices, at "
                         "most the chips JAX sees (0 = single-device "
                         "search)")
    ap.add_argument("--corpus-shards", type=int, default=0,
                    help="shard the CORPUS over this many partitions "
                         "(core/corpus_shard.py; 0 = replicated), one "
                         "shard per device; at most the device count")
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="traversal-tier vector storage (DESIGN.md §8); "
                         "int8 rescores the final candidates against the "
                         "fp32 tier unless --no-rescore")
    ap.add_argument("--no-rescore", action="store_true",
                    help="skip the fp32 rescoring pass (quantized "
                         "precisions only; shows the raw traversal-space "
                         "recall)")
    ap.add_argument("--tier", default="device",
                    choices=list(vecstore.PLACEMENTS),
                    help="fp32 rescore-tier placement (DESIGN.md §13): "
                         "'host' pins the rescore tier on the CPU backend "
                         "— device memory holds the quantized traversal "
                         "tier + graph only, and the re-rank gathers the "
                         "final ef rows per query across the boundary "
                         "(bitwise-identical results; needs a quantized "
                         "--precision with rescoring on)")
    ap.add_argument("--mutable", action="store_true",
                    help="serve through a DynamicIndex with per-batch "
                         "insert/delete churn (see module docstring)")
    ap.add_argument("--churn", type=int, default=None,
                    help="vectors inserted AND deleted per batch "
                         "(only with --mutable; default 64)")
    ap.add_argument("--refine-rounds", type=int, default=None,
                    help="localized propagation rounds per insert batch "
                         "(only with --mutable; default 2)")
    ap.add_argument("--optimize-layout", default=None,
                    choices=list(layout.ORDERS),
                    help="run the post-build layout pass (core/layout.py, "
                         "DESIGN.md §10) before serving: packed fixed-"
                         "degree adjacency + the chosen vertex renumbering; "
                         "results are bitwise-identical, ids stay in the "
                         "original numbering.  With --mutable, slots are "
                         "renumbered at startup and after every compact()")
    ap.add_argument("--filter-labels", type=int, default=0,
                    help="filtered serving: synthetic per-vertex labels in "
                         "[0, L); each query gets a random allowed-label "
                         "predicate (0 = unfiltered)")
    ap.add_argument("--selectivity", type=float, default=None,
                    help="fraction of the label space each query predicate "
                         "allows (only with --filter-labels; default 0.1)")
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine serving (serve/"
                         "ann_engine.py, DESIGN.md §12): a trace-driven "
                         "stream of small requests with mixed k/ef/filter "
                         "(plus insert/delete churn under --mutable) is "
                         "coalesced into jit-bucketed batches; reports "
                         "p50/p99 latency, QPS, occupancy, bucket count")
    ap.add_argument("--offered-qps", type=float, default=None,
                    help="trace arrival rate (only with --engine; default: "
                         "auto-calibrate to the measured batch capacity)")
    ap.add_argument("--requests", type=int, default=256,
                    help="trace length in queries (only with --engine)")
    ap.add_argument("--trace-seed", type=int, default=0,
                    help="trace RNG seed (only with --engine)")
    ap.add_argument("--mix-k", default="5,10",
                    help="comma-separated k menu the trace draws from "
                         "(only with --engine)")
    ap.add_argument("--mix-ef", default=None,
                    help="comma-separated ef menu the trace draws from "
                         "(only with --engine; default: just --ef)")
    ap.add_argument("--max-batch", type=int, default=64,
                    help="engine batch-size ceiling (only with --engine)")
    ap.add_argument("--quantum", type=int, default=4,
                    help="query batches per mutation drain when both "
                         "queues are backed up (only with --engine)")
    ap.add_argument("--max-pending", type=int, default=1024,
                    help="admission-control queue bound (only with "
                         "--engine; excess requests are shed and counted)")
    ap.add_argument("--churn-every", type=int, default=32,
                    help="queries between churn events in the trace (only "
                         "with --engine --mutable)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.visited_cap is not None and args.visited != "hashed":
        ap.error("--visited-cap only applies with --visited hashed "
                 "(dense mode would silently ignore it)")
    n_dev = len(jax.devices())
    if args.shards > n_dev or args.corpus_shards > n_dev:
        flag = "--shards" if args.shards > n_dev else "--corpus-shards"
        ap.error(f"{flag} {max(args.shards, args.corpus_shards)} exceeds "
                 f"the {n_dev} {jax.devices()[0].platform} device(s) JAX "
                 "sees; one shard runs per device, so run on a host with "
                 "that many chips (a TPU v5e host has 1 or 4)")
    if args.shards > 0 and args.mutable:
        ap.error("--mutable currently serves single-device (the mutation "
                 "path is not query-sharded); drop --shards")
    if args.corpus_shards > 0 and args.shards > 0:
        ap.error("--corpus-shards and --shards pick one sharding axis per "
                 "process; compose them via a 2-D mesh in a custom launcher")
    if args.corpus_shards > 0 and args.mutable:
        ap.error("--mutable serves the replicated layout; use "
                 "DynamicIndex.corpus_search for corpus-sharded mutation "
                 "serving")
    if not args.mutable and (args.churn is not None
                             or args.refine_rounds is not None):
        ap.error("--churn/--refine-rounds only apply with --mutable")
    if args.no_rescore and args.precision == "fp32":
        ap.error("--no-rescore only applies with --precision bf16/int8 "
                 "(fp32 traversal is already exact)")
    if args.tier == "host" and args.precision == "fp32":
        ap.error("--tier host places the fp32 RESCORE tier; at --precision "
                 "fp32 the fp32 buffer IS the traversal tier and must stay "
                 "device-resident")
    if args.tier == "host" and args.no_rescore:
        ap.error("--tier host without a rescore pass places nothing; drop "
                 "--no-rescore")
    if args.selectivity is not None and not args.filter_labels:
        ap.error("--selectivity only applies with --filter-labels")
    if args.filter_labels and not (args.selectivity is None
                                   or 0 < args.selectivity <= 1):
        ap.error("--selectivity must be in (0, 1]")
    if args.engine and args.shards > 0:
        ap.error("--engine shapes its own batches; query-sharding a "
                 "dynamic batch needs a custom worker (drop --shards)")
    if not args.engine and (args.offered_qps is not None
                            or args.mix_ef is not None):
        ap.error("--offered-qps/--mix-ef only apply with --engine")
    if args.engine and args.mutable and args.corpus_shards > 0:
        ap.error("--engine --mutable serves the replicated layout")

    if args.backend is not None:
        ops.set_backend(args.backend)

    blob = np.load(args.index)
    x = jnp.asarray(blob["x"])
    ids = jnp.asarray(blob["ids"])

    if args.engine:
        serve_engine(args, x, blob, ids)
        return
    if args.mutable:
        serve_mutable(args, x, jnp.asarray(blob["dists"]), ids)
        return

    (xt, ids, entry, rescore, bpv, lstore, sel, ef, words, ids_map,
     cs_idx, cs_mesh) = _static_setup(args, x, ids)

    mesh = None
    if args.shards > 0:
        mesh = make_mesh((args.shards,), ("data",),
                             devices=jax.devices()[:args.shards])
        # replicate the index across the mesh ONCE; the per-batch
        # device_put inside distributed_search then no-ops on x/ids
        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(mesh, PartitionSpec())
        xt = jax.tree.map(lambda a: jax.device_put(a, rep), xt)
        ids = jax.device_put(ids, rep)
        entry = jax.device_put(entry, rep)
        if rescore is not None and not vecstore.is_host(rescore):
            rescore = jax.device_put(rescore, rep)  # host tier stays put
        if ids_map is not None:
            ids_map = jax.device_put(ids_map, rep)

    def run_batch(q, fwords):
        if cs_idx is not None:
            return cs_idx.search(
                q, k=args.k, ef=ef, visited=args.visited,
                visited_cap=args.visited_cap, filter=fwords,
                mesh=cs_mesh)
        kw = dict(k=args.k, ef=ef, entry=entry, visited=args.visited,
                  visited_cap=args.visited_cap, rescore=rescore,
                  ids_map=ids_map)
        if lstore is not None:
            kw.update(labels=words, filter=fwords)
        if mesh is None:
            return search(xt, ids, q, **kw)
        return distributed_search(mesh, ("data",), xt, ids, q, **kw)

    lat, recs, preds = [], [], []
    for b in range(args.batches + 1):
        kb = jax.random.PRNGKey(100 + b)
        q = synthetic.queries_from(kb, x, args.batch_size)
        fw = (lab.random_query_filters(jax.random.fold_in(kb, 7),
                                       args.batch_size, args.filter_labels,
                                       sel)
              if lstore is not None else None)
        t0 = time.perf_counter()
        res = run_batch(q, fw)
        res.ids.block_until_ready()
        dt = time.perf_counter() - t0
        if b == 0:
            continue  # compile batch
        lat.append(dt)
        if lstore is None:
            gt = brute_force_knn(x, q, args.k)
            recs.append(recall_at_k(res.ids, gt))
        else:
            # recall against brute force over each query's ALLOWED subset,
            # plus the hard invariant: every returned id passes its predicate
            gt = lab.filtered_brute_force(x, q, fw, lstore.words, args.k)
            recs.append(lab.filtered_recall_at_k(res.ids, gt))
            preds.append(lab.predicate_fraction(res.ids, fw, lstore.words))

    qps = args.batch_size / (sum(lat) / len(lat))
    extra = ""
    if lstore is not None:
        extra = (f"filtered=1  selectivity={sel:g}  "
                 f"pred_ok={sum(preds)/len(preds):.3f}  ef={ef}  ")
    print(f"qps={qps:.0f}  p50={sorted(lat)[len(lat)//2]*1e3:.1f}ms  "
          f"recall@{args.k}={sum(recs)/len(recs):.3f}  {extra}"
          f"backend={ops.effective_backend()}  visited={args.visited}  "
          f"precision={args.precision}  bpv={bpv:.0f}  "
          f"rescore={int(rescore is not None)}  "
          f"tier={args.tier}  "
          f"opt_layout={args.optimize_layout or 'none'}  "
          f"shards={max(args.shards, 1)}  "
          f"corpus_shards={max(args.corpus_shards, 1)}")


def serve_engine(args, x, blob, ids):
    """--engine: continuous-batching serving (serve/ann_engine.py, §12).

    A synthetic open-loop trace (Poisson arrivals, per-request k/ef drawn
    from --mix-k/--mix-ef, with --filter-labels every other request carries
    a predicate, with --mutable a churn pair lands every --churn-every
    queries) is replayed against the engine.  A closed-loop warm-up replay
    first compiles the jit buckets and measures capacity (the default
    --offered-qps is 70% of it); the measured replay then reports
    p50/p99 latency, QPS, occupancy, and the bucket-trace count.
    """
    import dataclasses

    from repro.serve import ann_engine as AE

    k_choices = [int(s) for s in args.mix_k.split(",") if s.strip()]
    ef_choices = ([int(s) for s in args.mix_ef.split(",") if s.strip()]
                  if args.mix_ef else [args.ef])
    cfg = AE.EngineConfig(max_pending=args.max_pending,
                          max_batch=args.max_batch,
                          query_quantum=args.quantum,
                          ef_menu=tuple(sorted(set(ef_choices))))
    if max(k_choices) > min(cfg.k_cap, min(ef_choices)):
        raise SystemExit(f"--mix-k max {max(k_choices)} exceeds "
                         f"min(k_cap={cfg.k_cap}, ef={min(ef_choices)})")

    kq = jax.random.PRNGKey(9000 + args.trace_seed)
    q = np.asarray(synthetic.queries_from(kq, x, args.requests))

    # build the worker for the requested serving configuration
    mut_every, churn_vecs, churn_labs = 0, None, None
    if args.mutable:
        lstore, sel, _ = _filter_setup(args, x.shape[0])
        rounds = args.refine_rounds if args.refine_rounds is not None else 2
        idx = DynamicIndex(x, Pool(ids, jnp.asarray(blob["dists"])),
                           DynamicConfig(refine_rounds=rounds,
                                         precision=args.precision,
                                         tier=args.tier,
                                         layout=args.optimize_layout),
                           vertex_labels=(None if lstore is None
                                          else lstore.labels),
                           n_labels=(args.filter_labels
                                     if lstore is not None else None))
        worker = AE.DynamicWorker(idx, visited=args.visited,
                                  visited_cap=args.visited_cap)
        churn = args.churn if args.churn is not None else 16
        mut_every = args.churn_every
        n_churn = max(1, args.requests // max(mut_every, 1))
        churn_vecs = [np.asarray(synthetic.queries_from(
            jax.random.fold_in(kq, 100 + i), x, churn, noise=0.1))
            for i in range(n_churn)]
        if lstore is not None:
            churn_labs = [np.asarray(jax.random.randint(
                jax.random.fold_in(kq, 200 + i), (churn,), 0,
                args.filter_labels), np.int32) for i in range(n_churn)]
    else:
        (xt, gids, entry, rescore, _bpv, lstore, sel, _ef, words, ids_map,
         cs_idx, cs_mesh) = _static_setup(args, x, ids)
        if cs_idx is not None:
            worker = AE.ShardedWorker(cs_idx, mesh=cs_mesh,
                                      visited=args.visited,
                                      visited_cap=args.visited_cap)
        else:
            worker = AE.StaticWorker(xt, gids, entry=entry,
                                     visited=args.visited,
                                     visited_cap=args.visited_cap,
                                     rescore=rescore, labels=words,
                                     ids_map=ids_map)

    # every other request filtered (a mixed-predicate stream), the rest plain
    fwords = None
    if lstore is not None:
        fw = np.asarray(lab.random_query_filters(
            jax.random.fold_in(kq, 7), args.requests, args.filter_labels,
            sel))
        fwords = [fw[i] if i % 2 == 0 else None
                  for i in range(args.requests)]

    def make_trace(offered):
        rng = np.random.default_rng(args.trace_seed)
        return AE.synth_trace(rng, q, offered_qps=offered,
                              k_choices=k_choices, ef_choices=ef_choices,
                              fwords=fwords, mutation_every=mut_every,
                              churn_vectors=churn_vecs,
                              churn_labels=churn_labs)

    eng = AE.AnnEngine(worker, cfg)

    # closed-loop warm-up: everything arrives at t~0, so the big buckets
    # compile here and the drain rate measures the engine's capacity
    warm_rids = AE.replay(eng, [dataclasses.replace(ev, t=0.0)
                                for ev in make_trace(1.0)])
    for rid in warm_rids.values():
        eng.take_result(rid)
    capacity = max(eng.stats().qps, 1.0)
    eng.reset_stats()

    offered = (args.offered_qps if args.offered_qps is not None
               else 0.7 * capacity)
    trace = make_trace(offered)
    rids = AE.replay(eng, trace)
    s = eng.stats()

    extra = ""
    if args.mutable:
        extra = (f"mutations/s={s.mutations_per_sec:.0f}  "
                 f"live={idx.n_live}  ")
    else:
        # recall + the filtered hard invariant, per admitted request
        row_of = {ti: j for j, ti in enumerate(
            i for i, ev in enumerate(trace) if ev.kind == "query")}
        kmax = max(k_choices)
        gt_plain = np.asarray(brute_force_knn(x, jnp.asarray(q), kmax))
        recs, preds = [], []
        for ti, rid in rids.items():
            ev, res = trace[ti], eng.take_result(rid)
            if ev.fwords is None:
                recs.append(recall_at_k(res.ids[None],
                                        gt_plain[row_of[ti], :ev.k][None]))
            else:
                fwr = jnp.asarray(ev.fwords)[None]
                gt = lab.filtered_brute_force(x, jnp.asarray(q[row_of[ti]])[None],
                                              fwr, lstore.words, ev.k)
                recs.append(lab.filtered_recall_at_k(res.ids[None], gt))
                preds.append(lab.predicate_fraction(
                    jnp.asarray(res.ids)[None], fwr, lstore.words))
        extra = f"recall={sum(recs) / max(len(recs), 1):.3f}  "
        if preds:
            extra += f"pred_ok={sum(preds) / len(preds):.3f}  "

    print(f"engine=1  qps={s.qps:.0f}  offered={offered:.0f}  "
          f"p50={s.p50_ms:.1f}ms  p99={s.p99_ms:.1f}ms  "
          f"occupancy={s.mean_occupancy:.2f}  buckets={s.n_buckets}  "
          f"completed={s.n_completed}  rejected={s.n_rejected}  {extra}"
          f"backend={ops.effective_backend()}  visited={args.visited}  "
          f"precision={args.precision}  tier={args.tier}  "
          f"mutable={int(args.mutable)}  "
          f"corpus_shards={max(args.corpus_shards, 1)}")


def _static_setup(args, x, ids):
    """The frozen-index serving operands, shared by the fixed-batch path
    and the engine's StaticWorker/ShardedWorker: precision tier (§8),
    filtered-serving labels (§9), optional layout pass (§10), optional
    corpus sharding (§11)."""
    # the precision ladder (DESIGN.md §8): traversal reads the compact
    # tier; the fp32 array stays around only as the rescoring tier
    store = vecstore.encode(x, args.precision)
    xt = x if args.precision == "fp32" else store
    rescore = x if (args.precision != "fp32" and not args.no_rescore) else None
    bpv = store.bytes_per_vector()
    entry = medoid(xt)

    lstore, sel, ef = _filter_setup(args, x.shape[0])

    words = None if lstore is None else lstore.words
    ids_map = None
    if args.optimize_layout:
        # the post-build layout pass (DESIGN.md §10): every index-side
        # operand is permuted together and `ids_map` restores original
        # numbering on the way out, so gt scoring below is untouched
        opt = layout.optimize(xt, ids, order=args.optimize_layout,
                              rescore=rescore, labels=words, entry=entry)
        xt, ids, entry, rescore = opt.x, opt.graph_ids, opt.entry, opt.rescore
        ids_map = opt.inv
        if words is not None:
            words = opt.vwords

    cs_idx = cs_mesh = None
    if args.corpus_shards > 0:
        from repro.core import corpus_shard as CS
        # partition AFTER the optional layout pass (the §11 composition
        # contract: shards slice the permuted rows, ids_map restores the
        # caller's numbering owner-side).  --tier host keeps the rescore
        # tier off the shards entirely (§13).
        cs_idx = CS.shard(xt, ids, args.corpus_shards, rescore=rescore,
                          labels=words, ids_map=ids_map, entry=entry,
                          tier=args.tier)
        cs_mesh = make_mesh((args.corpus_shards,), ("data",))
    elif args.tier == "host" and rescore is not None:
        # host-cold placement (§13): wrap AFTER the layout pass so the
        # pinned tier holds the permuted rows the internal ids index
        rescore = vecstore.HostTier(rescore)
    return (xt, ids, entry, rescore, bpv, lstore, sel, ef, words, ids_map,
            cs_idx, cs_mesh)


def _filter_setup(args, n: int):
    """(LabelStore | None, selectivity, effective ef) for filtered serving.

    Labels are synthetic and deterministic (the saved index carries no
    attributes); the effective ef applies the §9.3 over-fetch policy
    (`core.search.overfetch_ef` — the same single source fig12
    benchmarks and validates) so ~k allowed survivors exist even at low
    selectivity.
    """
    if not args.filter_labels:
        return None, None, args.ef
    vlab = jax.random.randint(jax.random.PRNGKey(1234), (n,), 0,
                              args.filter_labels)
    lstore = lab.encode_labels(vlab, args.filter_labels)
    sel = args.selectivity if args.selectivity is not None else 0.1
    return lstore, sel, overfetch_ef(n, args.k, sel, ef=args.ef)


def serve_mutable(args, x, dists, ids):
    """--mutable: per-batch insert/delete churn through a DynamicIndex.

    Only batch 0 is excluded as the compile batch: a mid-run capacity
    doubling or auto-compaction changes buffer shapes and retraces the
    jits, and those seconds land in the reported latencies — faithful for
    an ops view of steady-state serving (stalls included), but use
    benchmarks/fig10_churn.py (which warms an exact replay) for clean
    mutation-throughput numbers.
    """
    rounds = args.refine_rounds if args.refine_rounds is not None else 2
    lstore, sel, ef = _filter_setup(args, x.shape[0])
    nl = args.filter_labels
    idx = DynamicIndex(x, Pool(ids, dists),
                       DynamicConfig(refine_rounds=rounds,
                                     precision=args.precision,
                                     tier=args.tier,
                                     layout=args.optimize_layout),
                       vertex_labels=(None if lstore is None
                                      else lstore.labels),
                       n_labels=nl if lstore is not None else None)
    churn = args.churn if args.churn is not None else 64
    mut_lat, lat, recs, preds = [], [], [], []
    for b in range(args.batches + 1):
        kb = jax.random.PRNGKey(100 + b)
        t0 = time.perf_counter()
        if churn > 0:
            idx.insert(synthetic.queries_from(kb, x, churn, noise=0.1),
                       vertex_labels=(None if lstore is None else np.asarray(
                           jax.random.randint(jax.random.fold_in(kb, 3),
                                              (churn,), 0, nl), np.int32)))
            live = idx.labels[:idx.size][np.asarray(idx.valid[:idx.size])]
            # oldest live = smallest labels: a sliding-window corpus.  Sort
            # first — under a layout permutation slot order is NOT label
            # order (core/layout.py)
            idx.delete(np.sort(live)[:churn])
        t_mut = time.perf_counter() - t0

        q = synthetic.queries_from(jax.random.fold_in(kb, 1), x,
                                   args.batch_size)
        fw = (lab.random_query_filters(jax.random.fold_in(kb, 7),
                                       args.batch_size, nl, sel)
              if lstore is not None else None)
        t0 = time.perf_counter()
        res = idx.search(q, k=args.k, ef=ef, visited=args.visited,
                         visited_cap=args.visited_cap,
                         rescore=False if args.no_rescore else None,
                         filter=fw)
        res.dists.block_until_ready()
        dt = time.perf_counter() - t0
        if b == 0:
            continue  # compile batch
        mut_lat.append(t_mut)
        lat.append(dt)
        gt = idx.exact_knn(q, args.k, filter=fw)
        if lstore is None:
            recs.append(recall_at_k(res.ids, gt))
        else:
            recs.append(lab.filtered_recall_at_k(res.ids, gt))
            # the hard invariant, mapped back from label space: every
            # returned external label's slot must pass its predicate
            # (the canonical check, lab.predicate_fraction, runs on slots)
            r_ids = np.asarray(res.ids)
            table = idx.labels[:idx.size]
            # argsort-backed lookup: identical to the plain binary search
            # without a layout permutation, correct with one
            sorter = np.argsort(table, kind="stable")
            pos = np.clip(np.searchsorted(table, np.clip(r_ids, 0, None),
                                          sorter=sorter),
                          0, idx.size - 1)
            slots = np.where(r_ids >= 0, sorter[pos], -1)
            preds.append(lab.predicate_fraction(jnp.asarray(slots), fw,
                                                idx.label_words()))

    qps = args.batch_size / (sum(lat) / len(lat))
    mut_per_s = 2 * churn / (sum(mut_lat) / len(mut_lat)) if churn else 0.0
    extra = ""
    if lstore is not None:
        extra = (f"filtered=1  selectivity={sel:g}  "
                 f"pred_ok={sum(preds)/len(preds):.3f}  ef={ef}  ")
    print(f"qps={qps:.0f}  p50={sorted(lat)[len(lat)//2]*1e3:.1f}ms  "
          f"recall@{args.k}={sum(recs)/len(recs):.3f}  {extra}"
          f"mutations/s={mut_per_s:.0f}  churn={churn}  "
          f"live={idx.n_live}  tomb={idx.tombstone_fraction:.2f}  "
          f"rounds={idx.rounds_run}  "
          f"backend={ops.effective_backend()}  visited={args.visited}  "
          f"precision={args.precision}  tier={args.tier}  "
          f"opt_layout={args.optimize_layout or 'none'}  mutable=1  "
          f"corpus_shards=1")


if __name__ == "__main__":
    main()
