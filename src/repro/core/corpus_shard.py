"""Corpus-sharded index: break the single-device memory ceiling.

Every other serving path replicates the full corpus per device —
`distributed_search` shards only *queries*, so N is capped by one device's
memory (ROADMAP ceiling 1).  This module shards the CORPUS: shard `s` of S
owns the contiguous row range [s·n_loc, (s+1)·n_loc) of the vectors, the
graph rows, the validity mask, the label words, the rescore tier, and the
layout `ids_map` — every O(N) operand — while per-query state (beam,
visited set, result heap) stays O(Q) and replicates.

The partition/id-map contract (DESIGN.md §11):

  * `n_loc = ceil(N / S)`; global id g lives on shard `g // n_loc` at local
    row `g % n_loc` (`shard_of` / `local_of` / `global_of`; the round-trip
    is the identity — tests/test_corpus_shard.py property tier).  The last
    shard may own fewer than n_loc real rows; its tail pads are
    unreachable (no graph edge, entry, or id map ever points >= N).
  * Graph rows are sharded by OWNER row but keep GLOBAL neighbor ids
    inside, so an edge crossing a shard boundary needs no rewriting.
  * Composition with the PR 6 layout pass: `shard_optimized` slices an
    `OptimizedIndex` along its PERMUTED rows — internal traversal ids are
    the permuted numbering, and each shard owns its slice of `inv`
    (`ids_map`), applied owner-side in the final gather.  global→(shard,
    local) therefore composes as `g_orig → perm[g_orig] → (shard, local)`.

The search (GGNN-style shard-local kernels, exact global semantics): every
step of the replicated beam search factors over corpus rows — the fused
`search_expand` kernel scores each neighbor against only that neighbor's
own vector row.  So each shard runs the kernel SHARD-LOCALLY on its slice
(neighbors it does not own masked to the -1 sentinel, exactly an empty
graph slot) and the per-slot outputs are reduced across shards with
order-free owner-combines: min for distances (+inf from non-owners), max
for ids (-1 from non-owners) and flags.  Exactly one shard contributes a
finite/valid value per slot, so the combine involves no fp re-association
— the reduced step is BITWISE the replicated step, for any shard count
(the invariance tier, tests/test_corpus_shard.py).  The final cross-shard
top-k reduction — owner-rescored candidates carrying re-based GLOBAL ids —
goes through the same order-free `ops.topr_merge` the build uses.

Entry points are owner-local in the same sense: the entry vertex lives on
one shard; its (tiny) dequantized row and validity bit are captured at
`shard()` time so the replicated beam seeds without a cross-shard gather.

Build side (`sharded_build`, the Wang et al. divide-and-conquer recipe):
per-partition GRNND builds — peak memory O(n_loc·D) per build — produce a
block-diagonal pool; cross-boundary candidates with true traversal-space
distances are then injected through the standard request staging and
stitched by `DynamicIndex`'s localized-frontier propagation rounds
(`core.dynamic._localized_round` over the full frontier), plus one
reverse-edge pass, until RNG descent has repaired the boundaries
(quality tier: tests/test_corpus_shard.py vs the test_recall.py floor).

Execution: `sharded_search(index, queries)` runs the S per-shard kernel
calls in one process (the replicated reference, also the 1-device serving
fallback); `core.distributed.corpus_sharded_search` runs the identical
body as a shard_map over a device mesh — one shard per device, collectives
for the owner-combines — and is bitwise-identical to the reference.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core import labels as L
from repro.core import pools as P
from repro.core import vecstore as VS
from repro.core.grnnd import GRNNDConfig, build_graph, reverse_edge_round
from repro.core.search import (
    SearchResult, _rescore_merge, _table_insert, _table_member,
    align_queries, default_visited_cap, medoid, unpad)
from repro.kernels import ops

__all__ = [
    "CorpusShardedIndex", "shard", "shard_optimized", "sharded_search",
    "sharded_build", "shard_bounds", "shard_of", "local_of", "global_of",
    "memory_report",
]


# ---------------------------------------------------------------------------
# partition layout / id maps
# ---------------------------------------------------------------------------

def shard_bounds(n: int, n_shards: int) -> tuple[tuple[int, ...], int]:
    """(row0 per shard, n_loc) for the contiguous equal partition of [0, n).

    `n_loc = ceil(n / n_shards)`; shard s owns global rows
    [row0_s, min(row0_s + n_loc, n)) — the last shard may own fewer, and
    its slice is padded to n_loc with unreachable rows.
    """
    assert n_shards >= 1 and n >= 1, (n, n_shards)
    n_loc = -(-n // n_shards)
    return tuple(s * n_loc for s in range(n_shards)), n_loc


def shard_of(g, n_loc: int):
    """Owning shard of global id(s) g."""
    return g // n_loc


def local_of(g, n_loc: int):
    """Local row of global id(s) g on its owning shard."""
    return g % n_loc


def global_of(s, loc, n_loc: int):
    """Global id of local row `loc` on shard `s` (inverse of the above)."""
    return s * n_loc + loc


# ---------------------------------------------------------------------------
# the sharded index
# ---------------------------------------------------------------------------

class CorpusShardedIndex(NamedTuple):
    """Per-shard stacked operands: every array's leading axis is the shard
    axis (S, n_loc, ...), ready to `device_put` with a sharded leading-dim
    PartitionSpec (one shard per device) or to loop over in process.

    `data` holds the traversal tier's stored bytes (fp32/bf16/int8 per the
    precision ladder); `scale`/`offset` are the frozen per-dim quantizer
    params, replicated (they are (D,), not O(N)).  `graphs` rows carry
    GLOBAL neighbor ids.  `rescores` is the fp32 exact tier, pre-
    dequantized so the owner-side re-rank is row-for-row the replicated
    rescore math; under `shard(tier="host")` it is instead a
    `vecstore.HostTier` over the UNSTACKED (N, D) tier — contiguous
    partitions make the flattened stacked index equal the global id, so
    the host gather indexes global ids directly and no per-shard device
    slice exists at all (DESIGN.md §13).  `entry_row`/`entry_valid`/
    `entry_words` capture the entry vertex's owner-side state at shard()
    time (see module docstring).
    """
    data: jnp.ndarray                    # (S, n_loc, D) stored bytes
    scale: jnp.ndarray | None            # (D,) frozen quantizer (int8)
    offset: jnp.ndarray | None           # (D,)
    graphs: jnp.ndarray                  # (S, n_loc, R) int32, GLOBAL ids
    row0s: jnp.ndarray                   # (S,) int32 first global row
    valids: jnp.ndarray | None           # (S, n_loc) bool
    rescores: object | None              # (S, n_loc, D) fp32 exact tier,
                                         #   or a host-pinned VS.HostTier
    vwords: jnp.ndarray | None           # (S, n_loc, W) packed label words
    ids_maps: jnp.ndarray | None         # (S, n_loc) int32 layout inv slice
    entry: jnp.ndarray                   # () int32 global entry id
    entry_row: jnp.ndarray               # (D,) fp32 dequantized entry row
    entry_valid: jnp.ndarray | None      # () bool — valid[entry]
    entry_words: jnp.ndarray | None      # (W,) — vwords[entry]
    n: int                               # true corpus size

    @property
    def n_shards(self) -> int:
        return int(self.data.shape[0])

    @property
    def n_loc(self) -> int:
        return int(self.data.shape[1])

    def search(self, queries, **kw) -> SearchResult:
        return sharded_search(self, queries, **kw)


def _stack_shards(a, row0s: Sequence[int], n_loc: int, fill):
    """Slice rows into (S, n_loc, ...) with `fill`-padded tails."""
    import numpy as np
    a = np.asarray(a)
    n = a.shape[0]
    out = np.full((len(row0s), n_loc) + a.shape[1:], fill, a.dtype)
    for s, row0 in enumerate(row0s):
        m = min(n_loc, n - row0)
        out[s, :m] = a[row0:row0 + m]
    return jnp.asarray(out)


def shard(
    x,
    graph,
    n_shards: int,
    *,
    valid=None,
    rescore=None,
    labels=None,
    ids_map=None,
    entry=None,
    tier: str = "device",
) -> CorpusShardedIndex:
    """Partition a built index into a `CorpusShardedIndex`.

    `x` is the traversal tier (fp32 array or VectorStore), `graph` a
    `pools.Pool` or raw (N, R) id array; `valid`/`rescore`/`labels`/
    `ids_map` are the same optional operands `core.search.search` takes,
    each sliced to its owner shard.  `entry` defaults to the medoid of the
    FULL corpus (computed here, while it is still in one piece — the
    sharded index stores only the entry's id, row, and flags).

    `tier` places the fp32 rescore tier (DESIGN.md §13): "device" slices
    it per shard like every other O(N) operand; "host" pins the whole
    dequantized tier on the CPU backend (`vecstore.HostTier`) — devices
    then hold int8 + graph only, and the re-rank gathers the final ef
    rows per query across the boundary, bitwise-equal either way.
    """
    assert tier in VS.PLACEMENTS, tier
    gids = graph.ids if hasattr(graph, "ids") else graph
    n = int(VS.parts(x)[0].shape[0])
    assert gids.shape[0] == n, (gids.shape, n)
    row0s, n_loc = shard_bounds(n, n_shards)

    if entry is None:
        entry = medoid(x, None if valid is None else jnp.asarray(valid))
    entry = jnp.asarray(entry, jnp.int32)
    entry_row = VS.take(x, entry)

    xd, xs, xo = VS.parts(x)
    vwords = None if labels is None else L.store_words(labels)
    # the dequantized exact tier: owner-side rescue math must be row-for-row
    # the replicated `VS.take(rescore, ·)` gather (bitwise contract)
    resc = None if rescore is None else VS.dequant(rescore)
    if resc is not None and tier == "host":
        # host placement keeps the tier UNSTACKED — the HostTier gathers
        # by global id, and global id == flattened stacked index anyway
        # (contiguous partitions; only the last shard pads)
        resc_field = VS.HostTier(resc)
    elif resc is not None:
        resc_field = _stack_shards(resc, row0s, n_loc, 0)
    else:
        resc_field = None
    idx = CorpusShardedIndex(
        data=_stack_shards(xd, row0s, n_loc, 0),
        scale=xs, offset=xo,
        graphs=_stack_shards(gids, row0s, n_loc, -1),
        row0s=jnp.asarray(row0s, jnp.int32),
        valids=(None if valid is None
                else _stack_shards(jnp.asarray(valid), row0s, n_loc, False)),
        rescores=resc_field,
        vwords=(None if vwords is None
                else _stack_shards(vwords, row0s, n_loc, 0)),
        ids_maps=(None if ids_map is None
                  else _stack_shards(jnp.asarray(ids_map), row0s, n_loc, -1)),
        entry=entry, entry_row=entry_row,
        entry_valid=(None if valid is None else jnp.asarray(valid)[entry]),
        entry_words=(None if vwords is None else vwords[entry]),
        n=n,
    )
    return idx


def shard_optimized(opt, n_shards: int,
                    tier: str = "device") -> CorpusShardedIndex:
    """Partition a PR 6 `layout.OptimizedIndex` (the composition contract):
    shards slice the PERMUTED rows; each shard owns its slice of `inv`, so
    returned ids come back in the caller's original numbering."""
    return shard(opt.x, opt.graph_ids, n_shards, valid=opt.valid,
                 rescore=opt.rescore, labels=opt.vwords,
                 ids_map=opt.inv, entry=opt.entry, tier=tier)


# ---------------------------------------------------------------------------
# owner-combines
# ---------------------------------------------------------------------------

def _cmin(parts, axes):
    """Min over local shard contributions, then over mesh axes.  Non-owners
    contribute +inf, so exactly one finite value survives per slot — no fp
    re-association, hence order-free and exact."""
    a = functools.reduce(jnp.minimum, parts)
    return a if axes is None else jax.lax.pmin(a, axes)


def _cmax_i32(parts, axes):
    """Max over int32 contributions (non-owners contribute the -1
    sentinel); same exactness argument as `_cmin`."""
    a = functools.reduce(jnp.maximum, parts)
    return a if axes is None else jax.lax.pmax(a, axes)


def _cor(parts, axes):
    """Logical OR across shards (non-owners contribute False)."""
    a = functools.reduce(jnp.logical_or, parts)
    if axes is None:
        return a
    return jax.lax.pmax(a.astype(jnp.int32), axes).astype(bool)


def _owner(ids, row0, n_own, n_loc):
    """(owned mask, clipped local rows) of global `ids` for one shard."""
    loc = ids - row0
    owned = (ids >= 0) & (loc >= 0) & (loc < n_own)
    return owned, jnp.clip(loc, 0, n_loc - 1)


# ---------------------------------------------------------------------------
# the corpus-sharded search body
# ---------------------------------------------------------------------------

def _corpus_body(
    data, scale, offset, graphs, row0s, queries, entry, entry_row,
    entry_valid, rescores, valids, ids_maps, vwords, entry_words, fwords,
    *,
    n: int,
    k: int,
    ef: int,
    max_steps: int,
    visited: str,
    visited_cap: int,
    axes: tuple | None,
) -> SearchResult:
    """The beam-search loop of `search._search_impl`, with every gather of
    O(N) state replaced by shard-local work + an owner-combine.

    Operands arrive with a leading LOCAL shard axis: the in-process
    reference passes the full (S, n_loc, ...) stacks with `axes=None`;
    the shard_map executor (core/distributed.py) passes each device its
    (1, n_loc, ...) slice plus the mesh axis names, and the `_c*` combines
    finish the reduction with collectives.  Both routes reduce the same S
    single-owner contributions with order-free min/max, so they are
    bitwise-identical to each other AND to the replicated search
    (tests/test_corpus_shard.py).
    """
    s_l, n_loc, _r = graphs.shape
    q = queries.shape[0]
    qrows = jnp.arange(q, dtype=jnp.int32)
    filtered = fwords is not None
    queries = queries.astype(jnp.float32)
    n_owns = [jnp.minimum(n_loc, n - row0s[s]) for s in range(s_l)]

    d_entry = ops.rowwise_sqdist(
        queries, jnp.broadcast_to(entry_row, queries.shape))
    if entry_valid is not None:
        d_entry = jnp.where(entry_valid, d_entry, jnp.inf)
    cand_ids = jnp.full((q, ef), -1, jnp.int32).at[:, 0].set(entry)
    cand_dists = jnp.full((q, ef), jnp.inf, jnp.float32).at[:, 0].set(d_entry)
    expanded = jnp.zeros((q, ef), bool)
    n_exp = jnp.zeros((q,), jnp.int32)

    if filtered:
        e_ok = jnp.any((entry_words[None, :] & fwords) != 0, axis=-1)
        e_ok = e_ok & jnp.isfinite(d_entry)
        res_ids = jnp.full((q, ef), -1, jnp.int32).at[:, 0].set(
            jnp.where(e_ok, entry, -1))
        res_dists = jnp.full((q, ef), jnp.inf, jnp.float32).at[:, 0].set(
            jnp.where(e_ok, d_entry, jnp.inf))

    entry_col = jnp.broadcast_to(entry, (q, 1)).astype(jnp.int32)
    if visited == "dense":
        vstate = jnp.zeros((q, n), bool).at[:, entry].set(True)
    else:
        vstate = _table_insert(jnp.full((q, visited_cap), -1, jnp.int32),
                               entry_col)
    # the kernel always probes an empty dummy table here: freshness against
    # the REAL visited set is refined below on GLOBAL ids (the local kernel
    # only sees local ids, which must not touch the id-keyed table)
    dummy = jnp.full((q, 1), -1, jnp.int32)

    def cond(state):
        frontier = (state[0] >= 0) & ~state[2]
        return (state[5] < max_steps) & jnp.any(frontier)

    def body(state):
        cand_ids, cand_dists, expanded, vstate, n_exp, steps = state[:6]
        frontier_d = jnp.where((cand_ids >= 0) & ~expanded, cand_dists,
                               jnp.inf)
        sel = jnp.argmin(frontier_d, axis=-1)                      # (Q,)
        active = jnp.isfinite(jnp.min(frontier_d, axis=-1))        # (Q,)
        sel_id = cand_ids[qrows, sel]
        expanded = expanded.at[qrows, sel].set(True)

        # owner-side fetch of the selected vertices' graph rows (neighbor
        # ids inside the rows are already global)
        parts = []
        for s in range(s_l):
            owned, loc = _owner(sel_id, row0s[s], n_owns[s], n_loc)
            parts.append(jnp.where(owned[:, None], graphs[s][loc], -1))
        nbrs = _cmax_i32(parts, axes)                              # (Q, R)
        nbrs = jnp.where(active[:, None] & (nbrs >= 0), nbrs, -1)

        # shard-local fused expansion: each shard scores the neighbors it
        # owns (others masked to the empty sentinel) on its own x slice
        dq_parts, ok_parts, al_parts = [], [], []
        for s in range(s_l):
            owned, loc = _owner(nbrs, row0s[s], n_owns[s], n_loc)
            nloc = jnp.where(owned, loc, -1)
            x_s = (data[s] if scale is None
                   else VS.VectorStore(data[s], scale, offset))
            out = ops.search_expand(
                x_s, queries, nloc, dummy,
                None if valids is None else valids[s],
                vwords[s] if filtered else None,
                fwords if filtered else None)
            # dummy table => the kernel's fresh IS its live/valid mask
            dq_parts.append(out[1])
            ok_parts.append(out[2])
            if filtered:
                al_parts.append(out[3])
        dq = _cmin(dq_parts, axes)
        ok = _cor(ok_parts, axes)
        nbrs = jnp.where(ok, nbrs, -1)
        if filtered:
            allowed = _cor(al_parts, axes)

        # visited-set logic runs replicated on GLOBAL ids — the same math
        # the replicated search applies (dense: exact bitmask; hashed: the
        # kernel's probe formula via search._table_member)
        if visited == "dense":
            seen = vstate[qrows[:, None], jnp.clip(nbrs, 0)]
            fresh = ok & ~seen
            vstate = vstate.at[qrows[:, None], jnp.clip(nbrs, 0)].max(fresh)
        else:
            fresh = ok & ~_table_member(vstate, nbrs)
            vstate = _table_insert(vstate, jnp.where(fresh, nbrs, -1))

        dq = jnp.where(fresh, dq, jnp.inf)
        n_exp = n_exp + jnp.sum(fresh, axis=-1, dtype=jnp.int32)

        all_ids = jnp.concatenate([cand_ids, jnp.where(fresh, nbrs, -1)],
                                  axis=-1)
        all_d = jnp.concatenate([cand_dists, dq], axis=-1)
        new_ids, new_d = ops.topr_merge(all_ids, all_d, ef)

        exp_src = jnp.where(expanded & (cand_ids >= 0), cand_ids, -2)
        new_expanded = jnp.any(
            new_ids[:, :, None] == exp_src[:, None, :], axis=-1)
        new_expanded = new_expanded | (new_ids < 0)

        next_state = (new_ids, new_d, new_expanded, vstate, n_exp, steps + 1)
        if filtered:
            keep = fresh & allowed
            res_ids, res_dists = ops.topr_merge(
                jnp.concatenate([state[6], jnp.where(keep, nbrs, -1)],
                                axis=-1),
                jnp.concatenate([state[7], jnp.where(keep, dq, jnp.inf)],
                                axis=-1),
                ef)
            next_state = next_state + (res_ids, res_dists)
        return next_state

    state = (cand_ids, cand_dists, expanded, vstate, n_exp, jnp.int32(0))
    if filtered:
        state = state + (res_ids, res_dists)
    state = jax.lax.while_loop(cond, body, state)
    cand_ids, cand_dists, n_exp = state[0], state[1], state[4]
    out_ids, out_dists = ((state[6], state[7]) if filtered
                          else (cand_ids, cand_dists))

    if rescores is not None:
        # the cross-shard top-k reduction: each shard re-ranks the final ef
        # candidates IT OWNS against its fp32 tier slice (+inf elsewhere,
        # ids already re-based to global), and the order-free `topr_merge`
        # finishes the reduce — the same primitive, and bitwise the
        # replicated rescore (single-owner distances, no re-association)
        d_parts = []
        for s in range(s_l):
            owned, loc = _owner(out_ids, row0s[s], n_owns[s], n_loc)
            rv = rescores[s][loc]                          # (Q, ef, D)
            diff = queries[:, None, :] - rv
            d_parts.append(jnp.where(owned, jnp.sum(diff * diff, axis=-1),
                                     jnp.inf))
        d_exact = _cmin(d_parts, axes)
        out_ids, out_dists = ops.topr_merge(out_ids, d_exact, ef)

    out_ids, out_dists = out_ids[:, :k], out_dists[:, :k]
    if ids_maps is not None:
        # owner-side slice of the layout pass's inverse permutation
        parts = []
        for s in range(s_l):
            owned, loc = _owner(out_ids, row0s[s], n_owns[s], n_loc)
            parts.append(jnp.where(owned, ids_maps[s][loc], -1))
        out_ids = jnp.where(out_ids >= 0, _cmax_i32(parts, axes), -1)
    return SearchResult(out_ids, out_dists, n_exp)


@functools.partial(
    jax.jit,
    static_argnames=("n", "k", "ef", "max_steps", "visited", "visited_cap",
                     "backend"))
def _reference_impl(data, scale, offset, graphs, row0s, queries, entry,
                    entry_row, entry_valid, rescores, valids, ids_maps,
                    vwords, entry_words, fwords, *, n, k, ef, max_steps,
                    visited, visited_cap, backend):
    """In-process execution: the full shard stacks, combines as plain
    jnp.min/max folds.  `backend` is part of the jit key only (kernels
    dispatch at trace time, the `search._search_impl` contract)."""
    del backend
    return _corpus_body(data, scale, offset, graphs, row0s, queries, entry,
                        entry_row, entry_valid, rescores, valids, ids_maps,
                        vwords, entry_words, fwords, n=n, k=k, ef=ef,
                        max_steps=max_steps, visited=visited,
                        visited_cap=visited_cap, axes=None)


def sharded_search(
    index: CorpusShardedIndex,
    queries: jnp.ndarray,
    *,
    k: int = 10,
    ef: int = 64,
    max_steps: int = 512,
    visited: str = "dense",
    visited_cap: int | None = None,
    filter=None,
    overfetch: int = 4,
    mesh=None,
    axes: Sequence[str] = ("data",),
) -> SearchResult:
    """Corpus-sharded beam search; bitwise-identical to the replicated
    `core.search.search` over the unsharded operands, for ANY shard count.

    Without `mesh` the S per-shard kernel calls run in one process (the
    replicated reference — every shard's slice is resident, so this mode
    proves semantics but not the memory ceiling).  With a `mesh` of
    exactly `index.n_shards` devices the identical body runs as a
    shard_map (one shard per device, owner-combines as collectives) via
    `core.distributed.corpus_sharded_search` — per-device memory then
    holds 1/S of every O(N) operand.

    `filter` is the per-query predicate in any `core.labels.query_words`
    form; the index must have been sharded with `labels=`.
    """
    assert ef >= k
    assert visited in ("dense", "hashed"), visited
    if filter is not None:
        assert index.vwords is not None, \
            "filtered search needs an index sharded with labels="
        fwords = L.query_words(filter, index.vwords.shape[-1])
        ef = max(ef, overfetch * k)
    else:
        fwords = None
    if visited == "dense":
        cap = 0
    else:
        cap = (visited_cap if visited_cap is not None
               else default_visited_cap(ef))
    queries, fwords, qn = align_queries(queries, fwords)
    host = VS.is_host(index.rescores)
    if host:
        # host-cold tier (DESIGN.md §13): traversal runs without the
        # rescore/ids_map operands and keeps the full ef beam (k=ef); the
        # returned GLOBAL ids drive the host gather, then the same
        # `_rescore_merge` program as the replicated host path re-ranks.
        # The deferred ids_map is the flattened stack — flat index ==
        # global id under contiguous partitions, so the single gather is
        # value-for-value the owner-side `_cmax_i32` fold.
        run_idx = index._replace(rescores=None, ids_maps=None)
        k_run = ef
    else:
        run_idx, k_run = index, k
    if mesh is not None:
        from repro.core import distributed as D
        res = D.corpus_sharded_search(
            mesh, axes, run_idx, queries, k=k_run, ef=ef,
            max_steps=max_steps, visited=visited, visited_cap=cap,
            fwords=fwords)
    else:
        res = _reference_impl(
            run_idx.data, run_idx.scale, run_idx.offset, run_idx.graphs,
            run_idx.row0s, queries, run_idx.entry, run_idx.entry_row,
            run_idx.entry_valid, run_idx.rescores, run_idx.valids,
            run_idx.ids_maps, run_idx.vwords, run_idx.entry_words, fwords,
            n=run_idx.n, k=k_run, ef=ef, max_steps=max_steps,
            visited=visited, visited_cap=cap,
            backend=ops.effective_backend())
    if not host:
        return unpad(res, qn)
    rv = index.rescores.gather(res.ids)                    # (Q, ef, D)
    flat_map = (None if index.ids_maps is None
                else index.ids_maps.reshape(-1))
    out_ids, out_dists = _rescore_merge(
        res.ids, rv, jnp.asarray(queries, jnp.float32), flat_map, k=k)
    return unpad(SearchResult(out_ids, out_dists, res.n_expanded), qn)


# ---------------------------------------------------------------------------
# sharded build: per-partition GRNND + cross-boundary merge-refine
# ---------------------------------------------------------------------------

def _cross_candidates(key, n: int, row0s, n_loc: int, c: int) -> jnp.ndarray:
    """(N, c) uniform global ids from OTHER shards for every vertex: draw
    r in [0, n - n_own(v)) and wrap around the owner's range."""
    rows = jnp.arange(n, dtype=jnp.int32)
    s = rows // n_loc
    row0 = s * n_loc
    n_own = jnp.minimum(n_loc, n - row0)
    span = jnp.maximum(n - n_own, 1)
    r = jax.random.randint(key, (n, c), 0, 2**31 - 1, jnp.int32)
    return ((row0 + n_own)[:, None] + r % span[:, None]) % n


def sharded_build(
    key: jax.Array,
    x,
    cfg: GRNNDConfig,
    n_shards: int,
    *,
    merge_rounds: int = 3,
    cross_candidates: int = 8,
) -> P.Pool:
    """Divide-and-conquer build (Wang et al., PAPERS.md): per-partition
    GRNND subgraphs, then cross-boundary merge-refine rounds.

    Each partition builds independently on its own slice (peak build
    memory O(n_loc·D·s) instead of O(N·D·s)); local pool ids are re-based
    to global and concatenated into a block-diagonal pool.  Each of the
    `merge_rounds` rounds then (1) injects `cross_candidates` random
    OTHER-shard candidates per vertex — true traversal-space distances via
    the fused gather kernel, staged through the standard order-free
    request pipeline — and (2) runs one localized-frontier propagation
    round (`core.dynamic._localized_round`, the DynamicIndex primitive)
    over the full frontier, so RNG descent redirects the injected edges
    into the boundary-crossing neighborhoods the independent builds could
    not see.  A reverse-edge pass between rounds symmetrizes them.

    Returns a standard global (N, R) `pools.Pool` — searchable replicated,
    or sharded again via `shard()` (quality tier:
    tests/test_corpus_shard.py vs the test_recall.py recall floor).
    """
    from repro.core.dynamic import _localized_round
    assert n_shards >= 1
    if n_shards == 1:
        return build_graph(key, x, cfg)
    xd, xs, xo = VS.parts(x)
    n = int(xd.shape[0])
    row0s, n_loc = shard_bounds(n, n_shards)
    assert n_loc > cfg.s, \
        f"shard size {n_loc} too small for s={cfg.s} init sampling"

    ids_parts, d_parts = [], []
    for s, row0 in enumerate(row0s):
        m = min(n_loc, n - row0)
        x_s = (VS.VectorStore(xd[row0:row0 + m], xs, xo) if xs is not None
               else xd[row0:row0 + m])
        p = build_graph(jax.random.fold_in(key, s), x_s, cfg)
        ids_parts.append(jnp.where(p.ids >= 0, p.ids + row0, -1))
        d_parts.append(p.dists)
    pool = P.Pool(jnp.concatenate(ids_parts), jnp.concatenate(d_parts))

    frontier = jnp.arange(n, dtype=jnp.int32)
    owners = jnp.repeat(frontier, cross_candidates)
    backend = ops.effective_backend()
    for t in range(merge_rounds):
        kt = jax.random.fold_in(jax.random.fold_in(key, 7919), t)
        cand = _cross_candidates(jax.random.fold_in(kt, 0), n, row0s,
                                 n_loc, cross_candidates).reshape(-1)
        d = ops.gather_sqdist(x, owners, cand)
        req = P.Requests(
            dst=jnp.concatenate([owners, cand]),
            src=jnp.concatenate([cand, owners]),
            dist=jnp.concatenate([d, d]),
        )
        pool = P.insert_requests(pool, req, cap=cfg.cap)
        pool = _localized_round(
            x, pool.ids, pool.dists, frontier, jax.random.fold_in(kt, 1),
            pairs=cfg.pairs_per_vertex, cap=cfg.cap, backend=backend)
        if t != merge_rounds - 1:
            pool = reverse_edge_round(pool, cfg)
    return pool


# ---------------------------------------------------------------------------
# memory accounting (the N-ceiling story, benchmarks/fig13)
# ---------------------------------------------------------------------------

def memory_report(index: CorpusShardedIndex) -> dict:
    """Bytes of O(N) index state per shard vs replicated-per-device.

    `per_shard` is what ONE device holds under corpus sharding (its slice
    of every O(N) operand plus the tiny replicated entry state);
    `replicated` is what the query-sharded layout puts on EVERY device
    (the same operands at full length).  Per-query search state (beam,
    visited table) is O(Q) in both layouts and excluded.
    """
    def nbytes(a):
        return 0 if a is None else int(a.size) * a.dtype.itemsize

    # a host-pinned rescore tier contributes ZERO device bytes (the §13
    # contract the fig15 smoke gates on); its footprint is reported
    # separately as host bytes
    host = VS.is_host(index.rescores)
    resc_dev = None if host else index.rescores
    sliced = (index.data, index.graphs, index.valids, resc_dev,
              index.vwords, index.ids_maps)
    per_slice = sum(nbytes(a) // index.n_shards for a in sliced)
    rep_small = (nbytes(index.scale) + nbytes(index.offset)
                 + nbytes(index.entry_row))
    # replicated layout: the true-N rows of every operand on every device
    frac = index.n / float(index.n_shards * index.n_loc)
    replicated = int(sum(nbytes(a) for a in sliced) * frac) + rep_small
    return {
        "n": index.n,
        "n_shards": index.n_shards,
        "n_loc": index.n_loc,
        "per_shard_bytes": per_slice + rep_small,
        "replicated_bytes": replicated,
        "rescore_device_bytes": nbytes(resc_dev) // index.n_shards,
        "rescore_host_bytes": index.rescores.host_bytes() if host else 0,
    }
