"""Distributed GRNND build: vertex-sharded shard_map over the device mesh.

The paper lists multi-GPU/distributed deployment as future work (§6); this
module implements it for TPU pods.  Layout:

  * vectors `x` are replicated (vector payloads are the gather-heavy side;
    at N·D ≤ a few GiB replication is the right trade — a dim-sharded
    variant with partial-distance all-reduce is sketched in DESIGN.md §4);
  * pools are sharded over vertices along the (possibly multi-axis) data
    dimension of the mesh;
  * each shard generates redirect requests from its local vertices; requests
    whose destination lives on another shard are exchanged — the exact
    variant all-gathers the (dst, src, dist) triples (tiny vs vector data),
    the optimized variant buckets them per destination shard and uses
    all_to_all (see EXPERIMENTS.md §Perf);
  * survivors never leave their shard (a vertex's own write buffer is local),
    so only the redirect triples travel.

Determinism: identical results for any shard count, because the merge stage
is the same order-free topr_merge dataflow as the single-device build.

Serving side — TWO sharding layouts, two ceilings (DESIGN.md §11.4):

  * `distributed_search` shards *queries* over the mesh (x and the graph
    replicated; per-query search state — beam + visited set — stays
    shard-local, no collectives inside the loop).  With `visited="hashed"`
    the per-shard state is O(q_loc · visited_cap), independent of N — the
    layout for "millions of users" traffic (DESIGN.md §6.4).  Throughput
    scales with devices; N stays capped by ONE device's memory.
  * `corpus_sharded_search` shards the *corpus* (core/corpus_shard.py):
    each device owns 1/S of the vectors, graph rows, labels, valid mask,
    rescore tier, and layout map, runs the fused expansion kernel on its
    slice every step, and order-free owner-combine collectives (pmin /
    pmax over single-owner contributions) reassemble the replicated beam
    — bitwise the single-device search for any shard count
    (tests/test_corpus_shard.py).  N scales with devices; every device
    sees every query, so per-step latency gains S collectives.

Both layouts reuse the same `topr_merge`-based order-free merges, which is
what makes their shard-count invariance mechanical rather than statistical.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PSpec


from repro.core import labels as L
from repro.core import pools as P
from repro.core import vecstore as VS
from repro.core.grnnd import (
    GRNNDConfig, _pair_requests_chunk, _reverse_requests,
    _sorted_requests_chunk)
from repro.core.search import (
    SearchResult, _rescore_merge, align_queries, medoid, search, unpad)
from repro.kernels import ops


def _local_round_requests(x, ids_loc, dists_loc, row0, key, cfg: GRNNDConfig):
    """Request generation for a shard of vertices [row0, row0 + n_loc)."""
    n_loc, r = ids_loc.shape
    fn = (_pair_requests_chunk if cfg.order == "disordered"
          else _sorted_requests_chunk)
    rows_local = row0 + jnp.arange(n_loc, dtype=jnp.int32)
    return fn(x, ids_loc, dists_loc, rows_local, key, cfg)


def _filter_to_local(req: P.Requests, row0, n_loc) -> P.Requests:
    """Re-base request destinations to local row indices; drop non-local.

    Self-inserts are dropped HERE, while dst and src are still in the same
    global id space; after re-basing, dst is shard-local and src global, so
    the staging-time dst == src filter would both miss true self-inserts
    and falsely kill genuine requests whose global src happens to equal the
    local row index — downstream staging must run with drop_self=False.
    """
    dst_local = req.dst - row0
    ok = ((req.dst >= 0) & (dst_local >= 0) & (dst_local < n_loc)
          & (req.dst != req.src))
    return P.Requests(
        dst=jnp.where(ok, dst_local, -1),
        src=req.src,
        dist=req.dist,
    )


def make_sharded_builder(
    mesh: Mesh,
    axes: Sequence[str],
    cfg: GRNNDConfig,
    comm: str = "allgather",
):
    """Returns jit-able build_round(x, pool, key, reverse=False) with
    pools vertex-sharded.

    `axes` are the mesh axis names carrying the vertex shard (e.g.
    ("data",) or ("pod", "data")).  `comm` selects the update rounds'
    redirect exchange: "allgather" (exact) or "a2a" (bucketed all_to_all,
    bounded payload, drops a bucket's overflow).  `reverse` (traced) makes
    the round a reverse-edge round (§3.6): the same staging and merge, fed
    with reverse requests and no kills, always through the exact
    all-gather exchange — one compiled program serves both kinds of
    round, and the staging sorts dominate its compile time on a TPU.
    """
    axes = tuple(axes)
    vspec = PSpec(axes)          # vertex-sharded arrays
    rspec = PSpec()              # replicated

    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]

    def shard_index():
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def round_body(x, ids_loc, dists_loc, key, reverse):
        n_loc, r = ids_loc.shape
        sidx = shard_index()
        row0 = sidx * n_loc
        key = jax.random.fold_in(key, sidx)
        m = n_loc * max(r, cfg.pairs_per_vertex)

        def pad(req):  # inactive tail: both branches emit m requests
            k = m - req.dst.shape[0]
            return P.Requests(jnp.pad(req.dst, (0, k), constant_values=-1),
                              jnp.pad(req.src, (0, k), constant_values=-1),
                              jnp.pad(req.dist, (0, k),
                                      constant_values=jnp.inf))

        def update(_):
            req, killed = _local_round_requests(
                x, ids_loc, dists_loc, row0, key, cfg)
            return pad(req), killed

        def reverse_edges(_):
            dst, src, dist = _reverse_requests(P.Pool(ids_loc, dists_loc),
                                               cfg.rho)
            req = P.Requests(dst.reshape(-1), (src + row0).reshape(-1),
                             dist.reshape(-1))
            return pad(req), jnp.zeros((n_loc, r), bool)

        redirect, killed = jax.lax.cond(reverse, reverse_edges, update, None)

        def gather_all(red):
            return P.Requests(
                dst=jax.lax.all_gather(red.dst, axes, tiled=True),
                src=jax.lax.all_gather(red.src, axes, tiled=True),
                dist=jax.lax.all_gather(red.dist, axes, tiled=True),
            )

        def bucket_a2a(red):
            # fixed cap per (src shard, dst shard), sized for update rounds:
            # expected redirects/bucket ≈ n_loc · pairs / n_shards; 2x slack
            cap = max(2 * n_loc * cfg.pairs_per_vertex // max(n_shards, 1), r)
            dst_shard = jnp.where(red.dst >= 0, red.dst // n_loc, n_shards)
            buckets_i = jnp.full((n_shards, cap), -1, jnp.int32)
            buckets_s = jnp.full((n_shards, cap), -1, jnp.int32)
            buckets_d = jnp.full((n_shards, cap), jnp.inf, jnp.float32)
            order = jnp.argsort(dst_shard, stable=True)
            ds = dst_shard[order]
            idx = jnp.arange(ds.shape[0], dtype=jnp.int32)
            # rank within the shard's run (no prefix scan: see pools._stage)
            first = jnp.full((n_shards + 1,), ds.shape[0],
                             jnp.int32).at[ds].min(idx)
            rank = idx - first[ds]
            okk = (rank < cap) & (ds < n_shards)
            row = jnp.where(okk, ds, n_shards)
            buckets_i = buckets_i.at[row, rank].set(red.dst[order],
                                                    mode="drop")
            buckets_s = buckets_s.at[row, rank].set(red.src[order],
                                                    mode="drop")
            buckets_d = buckets_d.at[row, rank].set(red.dist[order],
                                                    mode="drop")
            a2a = functools.partial(
                jax.lax.all_to_all,
                axis_name=axes if len(axes) > 1 else axes[0],
                split_axis=0, concat_axis=0, tiled=True)
            return P.Requests(
                dst=a2a(buckets_i).reshape(-1),
                src=a2a(buckets_s).reshape(-1),
                dist=a2a(buckets_d).reshape(-1),
            )

        def stage(red_all):
            local_red = _filter_to_local(red_all, row0, n_loc)
            return P.group_requests(local_red, n_loc, cfg.cap,
                                    drop_self=False)

        if comm == "allgather":
            staged_i, staged_d = stage(gather_all(redirect))
        else:
            # reverse rounds take the exact exchange whatever `comm` is:
            # their requests pile onto the shards holding popular
            # neighbours, which the update-sized buckets would drop
            staged_i, staged_d = jax.lax.cond(
                reverse, lambda red: stage(gather_all(red)),
                lambda red: stage(bucket_a2a(red)), redirect)

        # survivors stay aligned in their shard (perf iteration g1):
        # only redirects go through the grouped-request path
        surv_ids = jnp.where(killed, -1, ids_loc)
        surv_dists = jnp.where(killed, jnp.inf, dists_loc)
        ids2 = jnp.concatenate([surv_ids, staged_i], axis=-1)
        d2 = jnp.concatenate([surv_dists, staged_d], axis=-1)
        return ops.topr_merge(ids2, d2, r)

    sharded = jax.shard_map(
        round_body, mesh=mesh,
        in_specs=(rspec, vspec, vspec, rspec, rspec),
        out_specs=(vspec, vspec),
        check_vma=False,
    )

    def build_round(x, pool: P.Pool, key, reverse=False) -> P.Pool:
        ids, dists = sharded(x, pool.ids, pool.dists, key,
                             jnp.asarray(reverse))
        return P.Pool(ids, dists)

    return build_round


def sharded_build_graph(
    mesh: Mesh,
    axes: Sequence[str],
    key: jax.Array,
    x: jnp.ndarray,
    cfg: GRNNDConfig,
    comm: str = "allgather",
) -> P.Pool:
    """Full distributed build: init (replicated math, sharded layout) + rounds."""
    n = x.shape[0]
    vshard = NamedSharding(mesh, PSpec(tuple(axes)))
    rshard = NamedSharding(mesh, PSpec())

    k_init, k_rounds = jax.random.split(key)
    # init on one device: its Pallas kernels cannot be auto-partitioned
    # over a mesh (Mosaic calls run per device only inside a shard_map)
    one = jax.sharding.SingleDeviceSharding(mesh.devices.flat[0])
    pool = P.init_random(k_init, jax.device_put(x, one), cfg.s, cfg.r)
    x = jax.device_put(x, rshard)
    pool = P.Pool(jax.device_put(pool.ids, vshard),
                  jax.device_put(pool.dists, vshard))

    round_fn = jax.jit(make_sharded_builder(mesh, axes, cfg, comm=comm))

    for t1 in range(cfg.t1):
        for t2 in range(cfg.t2):
            k = jax.random.fold_in(jax.random.fold_in(k_rounds, t1), t2)
            pool = round_fn(x, pool, k, False)
        if t1 != cfg.t1 - 1:
            pool = round_fn(x, pool, k_rounds, True)
    return pool


@functools.lru_cache(maxsize=32)
def _sharded_search_fn(mesh: Mesh, axes: tuple, k: int, ef: int,
                       max_steps: int, visited: str, visited_cap: int | None,
                       has_valid: bool, quantized: bool, has_rescore: bool,
                       has_filter: bool, has_map: bool, backend: str,
                       overfetch: int = 4):
    """One jitted shard_map per (mesh, axes, search-config) — cached so
    repeated serving batches reuse the compiled executable instead of
    re-tracing per call.  `has_valid` selects the tombstone-masked variant
    (an extra replicated operand); the static path keeps the original
    maskless trace.  `quantized`/`has_rescore` (the precision ladder,
    DESIGN.md §8) likewise select variants with the store's scale/offset
    and the fp32 rescore tier as extra replicated operands — the store is
    passed FLATTENED (data, scale, offset) so every shard_map operand is a
    plain array and the in_specs stay structural.  `has_filter` (filtered
    search, DESIGN.md §9) selects the predicate variant: the (N, W) vertex
    label words replicate like x, while the (Q, W) per-query allowed words
    shard WITH the queries — and the flag lives in this cache key, so a
    filtered batch can never reuse an unfiltered executable (or vice
    versa).  `has_map` selects the optimized-layout variant (core/
    layout.py): the (N,) inverse permutation replicates like the graph
    and each shard applies it to its own result slice — a per-row gather,
    so shard invariance is untouched.  `overfetch` is the inner search's
    filtered-widening factor — in the cache key because the host-tier
    path (below) pre-widens ef itself and runs with overfetch=1, and the
    two configurations must never share an executable.  `backend` is
    unused in the body but part of the cache key:
    the inner search dispatches kernels at trace time (same contract as
    search._search_impl)."""
    del backend
    qspec = PSpec(axes)
    rspec = PSpec()

    def body(x_r, graph_r, q_loc, entry_r, *extras):
        it = iter(extras)
        x_in = (VS.VectorStore(x_r, next(it), next(it)) if quantized
                else x_r)
        rescore = next(it) if has_rescore else None
        valid = next(it) if has_valid else None
        ids_map = next(it) if has_map else None
        vwords = next(it) if has_filter else None
        fwords = next(it) if has_filter else None
        return search(x_in, graph_r, q_loc, k=k, ef=ef, max_steps=max_steps,
                      entry=entry_r, visited=visited, visited_cap=visited_cap,
                      valid=valid, rescore=rescore,
                      labels=vwords, filter=fwords, ids_map=ids_map,
                      overfetch=overfetch)

    n_extra = 2 * quantized + has_rescore + has_valid + has_map
    in_specs = ((rspec, rspec, qspec, rspec) + (rspec,) * n_extra
                + ((rspec, qspec) if has_filter else ()))
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=SearchResult(qspec, qspec, qspec),
        check_vma=False,
    ))


def distributed_search(
    mesh: Mesh,
    axes: Sequence[str],
    x,
    graph_ids: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int = 10,
    ef: int = 64,
    max_steps: int = 512,
    entry: jnp.ndarray | None = None,
    visited: str = "dense",
    visited_cap: int | None = None,
    valid: jnp.ndarray | None = None,
    rescore=None,
    labels=None,
    filter=None,
    ids_map: jnp.ndarray | None = None,
) -> SearchResult:
    """Query-sharded beam search over the mesh.

    `axes` are the mesh axis names carrying the query shard.  x and the
    graph are replicated; each shard runs the unmodified `core.search.search`
    on its query slice, so results are bitwise-identical to the single-device
    search for any shard count (no cross-shard state exists).  Queries are
    padded so each shard's slice is a multiple of `search.Q_ALIGN` rows
    (`search.align_queries`) and the pad rows sliced off.

    `x` may be a VectorStore (the precision ladder): the traversal tier
    replicates at its compact storage width — bf16 halves and int8 quarters
    the per-device footprint of the replicated corpus, which is exactly
    what bounds the serving mesh's maximum N.  `rescore` is the optional
    fp32 exact tier for the post-beam re-rank (core/search.py), also
    replicated.

    `valid` is the dynamic index's tombstone mask (core/dynamic.py).  It is
    replicated here like x and the graph (query sharding); under VERTEX
    sharding (the build layout) the mask shards with the pools instead —
    each shard owns the validity of its own vertex rows.

    `labels`/`filter` are the filtered-search predicate (core/labels.py,
    DESIGN.md §9): the packed vertex words replicate with the corpus; the
    per-query allowed words are a PER-QUERY payload and shard (and pad)
    with the queries.  Filtering stays embarrassingly parallel — the
    route-through beam and result heap are per-query state — so shard
    invariance holds bitwise exactly as in the unfiltered path.

    `ids_map` is the optimized-layout inverse permutation (core/layout.py,
    `OptimizedIndex.inv`), replicated like the graph; each shard maps its
    own returned ids back to original numbering.

    A `vecstore.HostTier` rescore selects the host-cold placement
    (DESIGN.md §13): the tier is never replicated onto the mesh at all —
    the shards traverse without it (full-ef results, ids_map deferred),
    the final ids cross to the host once per batch, and the shared
    `_rescore_merge` program finishes — bitwise the device-resident path.
    """
    axes = tuple(axes)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    if visited == "dense":
        visited_cap = None  # unused; normalized to one cache entry (as search())

    if entry is None:
        entry = medoid(x, valid)  # once, replicated — not once per shard

    vwords = fwords = None
    if filter is not None:
        assert labels is not None, "filtered search needs a label store"
        vwords = L.store_words(labels)
        fwords = L.query_words(filter, vwords.shape[1])

    host = VS.is_host(rescore)
    if host:
        # pre-apply the inner search's filtered widening (its default
        # overfetch=4), then run k=ef with overfetch=1 so the shards
        # return the FULL beam/heap the host re-rank needs; rescore and
        # ids_map stay off the mesh and are applied after the gather
        ef_run = max(ef, 4 * k) if filter is not None else ef
        k_run, of_run = ef_run, 1
    else:
        ef_run, k_run, of_run = ef, k, 4

    q_in = queries  # pre-pad queries, for the host-side re-rank
    queries, fwords, qn = align_queries(queries, fwords, n_shards)

    xd, xs, xo = VS.parts(x)
    quantized = xs is not None
    sharded = _sharded_search_fn(mesh, axes, k_run, ef_run, max_steps,
                                 visited, visited_cap, valid is not None,
                                 quantized,
                                 rescore is not None and not host,
                                 filter is not None,
                                 ids_map is not None and not host,
                                 ops.effective_backend(), overfetch=of_run)
    rep = NamedSharding(mesh, PSpec())
    xd = jax.device_put(xd, rep)
    graph_ids = jax.device_put(graph_ids, rep)
    qsharding = NamedSharding(mesh, PSpec(axes))
    queries = jax.device_put(queries, qsharding)
    extra = ()
    if quantized:
        extra += (jax.device_put(xs, rep), jax.device_put(xo, rep))
    if rescore is not None and not host:
        extra += (jax.device_put(rescore, rep),)
    if valid is not None:
        extra += (jax.device_put(valid, rep),)
    if ids_map is not None and not host:
        extra += (jax.device_put(ids_map, rep),)
    if filter is not None:
        extra += (jax.device_put(vwords, rep),
                  jax.device_put(fwords, qsharding))
    # entry is replicated like x: a medoid computed from committed arrays
    # lives on one device and would not join the mesh by itself
    res = sharded(xd, graph_ids, queries, jax.device_put(entry, rep), *extra)
    res = unpad(res, qn)
    if host:
        rv = rescore.gather(res.ids)                       # (Q, ef, D)
        out_ids, out_dists = _rescore_merge(
            res.ids, rv, jnp.asarray(q_in, jnp.float32), ids_map, k=k)
        return SearchResult(out_ids, out_dists, res.n_expanded)
    return res


@functools.lru_cache(maxsize=32)
def _corpus_search_fn(mesh: Mesh, axes: tuple, n: int, k: int, ef: int,
                      max_steps: int, visited: str, visited_cap: int,
                      has_valid: bool, quantized: bool, has_rescore: bool,
                      has_filter: bool, has_map: bool, backend: str):
    """One jitted shard_map per (mesh, axes, corpus-search config) — the
    corpus-sharded sibling of `_sharded_search_fn`, same caching contract.

    Every O(N) operand (data, graph rows, row offsets, and the optional
    valid / rescore / ids_map / label-word slices) arrives STACKED with a
    leading shard axis and is sharded along `axes` on that axis — each
    device holds a (1, n_loc, ...) slice, which is exactly the local-shard
    view `corpus_shard._corpus_body` expects.  Queries, the entry state,
    and the per-query predicate words replicate: under corpus sharding
    every device walks every query, and the owner-combines inside the body
    (`lax.pmin`/`pmax` over `axes`) reassemble the replicated beam.  The
    body's outputs are identical on all devices (single-owner combines,
    deterministic ops), so the out_specs are replicated.  `n` (the true
    corpus size, distinct from S·n_loc under padding) and `backend` are
    cache-key-only like everywhere else in this module."""
    del backend
    from repro.core.corpus_shard import _corpus_body
    sspec = PSpec(axes)   # stacked shard-major operands, split on axis 0
    rspec = PSpec()

    def body(data, graphs, row0s, q_r, entry_r, entry_row_r, *extras):
        it = iter(extras)
        scale = next(it) if quantized else None
        offset = next(it) if quantized else None
        rescores = next(it) if has_rescore else None
        valids = next(it) if has_valid else None
        entry_valid = next(it) if has_valid else None
        ids_maps = next(it) if has_map else None
        vwords = next(it) if has_filter else None
        entry_words = next(it) if has_filter else None
        fwords = next(it) if has_filter else None
        return _corpus_body(
            data, scale, offset, graphs, row0s, q_r, entry_r, entry_row_r,
            entry_valid, rescores, valids, ids_maps, vwords, entry_words,
            fwords, n=n, k=k, ef=ef, max_steps=max_steps, visited=visited,
            visited_cap=visited_cap, axes=axes)

    in_specs = ((sspec, sspec, sspec, rspec, rspec, rspec)
                + (rspec, rspec) * quantized
                + (sspec,) * has_rescore
                + (sspec, rspec) * has_valid
                + (sspec,) * has_map
                + (sspec, rspec, rspec) * has_filter)
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=SearchResult(rspec, rspec, rspec),
        check_vma=False,
    ))


def corpus_sharded_search(
    mesh: Mesh,
    axes: Sequence[str],
    index,
    queries: jnp.ndarray,
    *,
    k: int,
    ef: int,
    max_steps: int,
    visited: str,
    visited_cap: int,
    fwords: jnp.ndarray | None,
) -> SearchResult:
    """Run a `corpus_shard.CorpusShardedIndex` over the mesh, one shard per
    device slot along `axes`.

    This is the executor behind `corpus_shard.sharded_search(mesh=...)` —
    arguments arrive normalized (ef widened, visited_cap resolved, the
    filter already packed to (Q, W) words); user code should call that
    wrapper.  The mesh's shard count along `axes` must equal
    `index.n_shards` — the partition is baked into the stacked arrays, not
    re-derived here.
    """
    axes = tuple(axes)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    assert n_shards == index.n_shards, \
        (f"mesh carries {n_shards} shards along {axes} but the index was "
         f"partitioned into {index.n_shards}")

    quantized = index.scale is not None
    fn = _corpus_search_fn(mesh, axes, index.n, k, ef, max_steps, visited,
                           visited_cap, index.valids is not None, quantized,
                           index.rescores is not None, fwords is not None,
                           index.ids_maps is not None,
                           ops.effective_backend())
    sh = NamedSharding(mesh, PSpec(axes))
    rep = NamedSharding(mesh, PSpec())
    args = (jax.device_put(index.data, sh),
            jax.device_put(index.graphs, sh),
            jax.device_put(index.row0s, sh),
            jax.device_put(queries, rep),
            jax.device_put(index.entry, rep),
            jax.device_put(index.entry_row, rep))
    if quantized:
        args += (jax.device_put(index.scale, rep),
                 jax.device_put(index.offset, rep))
    if index.rescores is not None:
        args += (jax.device_put(index.rescores, sh),)
    if index.valids is not None:
        args += (jax.device_put(index.valids, sh),
                 jax.device_put(index.entry_valid, rep))
    if index.ids_maps is not None:
        args += (jax.device_put(index.ids_maps, sh),)
    if fwords is not None:
        args += (jax.device_put(index.vwords, sh),
                 jax.device_put(index.entry_words, rep),
                 jax.device_put(fwords, rep))
    return fn(*args)


def sharded_apply_requests(
    mesh: Mesh,
    axes: Sequence[str],
    pool: P.Pool,
    req: P.Requests,
    cap: int | None = None,
) -> P.Pool:
    """Route a flat insertion-request batch to the owning vertex shards.

    The dynamic-index mutation primitive under the build's vertex-sharded
    layout (DESIGN.md §7): request destinations are GLOBAL vertex ids; each
    shard all-gathers the (tiny) triples, filters to its own row range with
    the same `_filter_to_local` re-basing the build rounds use, and merges
    through the local staging pipeline.  Determinism: identical to the
    single-device `pools.insert_requests` for any shard count, because the
    merge is the same order-free topr_merge dataflow.

    The tombstone mask needs no exchange at all — validity is a per-vertex
    property, so each shard owns the (n_loc,) slice of the mask for its own
    rows and deletes are a purely local scatter.
    """
    axes = tuple(axes)
    vspec = PSpec(axes)
    rspec = PSpec()
    cap = cap if cap is not None else pool.r

    def body(ids_loc, dists_loc, dst, src, dist):
        n_loc, r = ids_loc.shape
        sidx = jnp.int32(0)
        for a in axes:
            sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
        row0 = sidx * n_loc
        local = _filter_to_local(P.Requests(dst, src, dist), row0, n_loc)
        staged_i, staged_d = P.group_requests(local, n_loc, cap,
                                              drop_self=False)
        ids2 = jnp.concatenate([ids_loc, staged_i], axis=-1)
        d2 = jnp.concatenate([dists_loc, staged_d], axis=-1)
        return ops.topr_merge(ids2, d2, r)

    ids, dists = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(vspec, vspec, rspec, rspec, rspec),
        out_specs=(vspec, vspec),
        check_vma=False,
    ))(pool.ids, pool.dists, req.dst, req.src, req.dist)
    return P.Pool(ids, dists)
