"""Batched best-first graph search over a GRNND/RNN-Descent graph.

Standard greedy beam search (the "fixed search algorithm" the paper uses to
compare indices): a candidate list of size `ef` per query, expand the closest
unexpanded candidate, push its unvisited neighbors, stop when every list
entry is expanded.  Fully batched over queries with jax.lax.while_loop.

The production pieces (DESIGN.md §6):

  * the expansion step — gather the selected vertex's R neighbor vectors,
    compute query->neighbor distances, probe the visited set — is one fused
    op (`ops.search_expand`, kernels/search_expand.py) with a ref.py oracle;
  * the visited set is selectable: `visited="dense"` keeps the exact (Q, N)
    bitmask (right at reproduction scale), `visited="hashed"` replaces it
    with a fixed-size per-query open-addressed table of `visited_cap` int32
    slots, making search memory O(Q·H) independent of N.  Collisions and
    capacity misses only cause harmless re-expansions, never false skips;
    with `visited_cap >= N` the hashed path is provably collision-free and
    bitwise-identical to the dense reference (tests/test_search_parity.py);
  * the per-step beam merge is the deduplicating `ops.topr_merge` primitive
    the build path already uses — no full (Q, ef+R) argsort per step, and
    re-entering duplicates (possible under hash capacity misses) are
    absorbed instead of crowding the beam;
  * filtered search (`labels=`/`filter=`, core/labels.py, DESIGN.md §9)
    evaluates a per-query label predicate inside the same fused expansion
    op and accumulates predicate-passing vertices in a separate result
    heap — the beam itself stays unfiltered (route-through), so graph
    connectivity survives masking.

Query sharding over a device mesh lives in `core.distributed.
distributed_search` (x and graph replicated, queries sharded — searches are
embarrassingly parallel over queries).  CORPUS sharding — each device owns
1/S of the vectors/graph/labels/rescore tier and this loop's per-step
gathers become shard-local kernel calls plus order-free owner-combines —
lives in `core.corpus_shard` (DESIGN.md §11); that module mirrors this
loop line-for-line and is locked to it by a bitwise invariance tier
(tests/test_corpus_shard.py), so semantic changes here must land there in
the same commit.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import labels as L
from repro.core import vecstore as VS
from repro.kernels import ops
from repro.kernels.ref import visited_probe_positions


class SearchResult(NamedTuple):
    ids: jnp.ndarray     # (Q, k) int32
    dists: jnp.ndarray   # (Q, k) float32
    n_expanded: jnp.ndarray  # (Q,) int32 — distance computations proxy


def medoid(x, valid: jnp.ndarray | None = None) -> jnp.ndarray:
    """Entry point: vertex nearest to the dataset centroid.

    With a `valid` mask (dynamic index: tombstones + unallocated padded
    rows, core/dynamic.py), both the centroid and the argmin are restricted
    to live rows, so the entry is always a live vertex.  `x` may be a
    VectorStore: the centroid is taken over the dequantized corpus (a
    one-shot startup computation, not a hot path) so the entry choice
    matches what the traversal distances will see.
    """
    if valid is None:
        c = jnp.mean(VS.dequant(x), axis=0, keepdims=True)
        return jnp.argmin(ops.pairwise_sqdist(c, x)[0]).astype(jnp.int32)
    v = valid.astype(jnp.float32)
    c = (jnp.sum(VS.dequant(x) * v[:, None], axis=0)
         / jnp.maximum(jnp.sum(v), 1.0))[None, :]
    d = jnp.where(valid, ops.pairwise_sqdist(c, x)[0], jnp.inf)
    return jnp.argmin(d).astype(jnp.int32)


EF_CEILING = 512  # §9.3: past this, O(ef²) beam maintenance dominates

# Query batches (each shard's, when queries are sharded) run padded to a
# multiple of this: the TPU compiler takes minutes on a (Q, N) visited
# array whose Q is not a whole sublane tile (Q = 251: 379 s, Q = 256:
# 26 s, at N = 200k).  Pad rows repeat row 0;
# per-query independence of the beam loop keeps them invisible.
Q_ALIGN = 8


def align_queries(queries, fwords=None, shards: int = 1):
    """-> (queries, fwords, Q): both padded so that each of `shards`
    equal row blocks (query-sharded search) is a multiple of Q_ALIGN."""
    qn = queries.shape[0]
    pad = (-qn) % (Q_ALIGN * shards)

    def rep(a):
        if a is None or pad == 0:
            return a
        return jnp.concatenate(
            [a, jnp.broadcast_to(a[:1], (pad,) + tuple(a.shape[1:]))])

    return rep(jnp.asarray(queries)), rep(fwords), qn


def unpad(res: SearchResult, qn: int) -> SearchResult:
    """The first qn rows of a result computed on padded queries."""
    if res.ids.shape[0] == qn:
        return res
    return SearchResult(res.ids[:qn], res.dists[:qn], res.n_expanded[:qn])


def overfetch_ef(n: int, k: int, selectivity: float, ef: int) -> int:
    """The §9.3 low-selectivity over-fetch policy, in one place (serving
    and benchmarks must stay in sync with what DESIGN.md documents and
    fig12 validates): widen the beam toward ~4·k/selectivity so ~k
    allowed survivors exist, clamped at the corpus size and at the
    practical ceiling — beyond it the per-step `topr_merge` dedup
    (O(ef²) work and mask memory) costs more than the recall it buys,
    and traffic that needs more wants a pre-partitioned index."""
    return max(ef, min(n, math.ceil(4 * k / selectivity), EF_CEILING))


def default_visited_cap(ef: int) -> int:
    """Default hashed-table size: O(ef·expansion), independent of N.

    Each expansion inserts at most R fresh ids and the beam retires after
    ~ef expansions, so 8·ef slots keep the load factor low enough that
    capacity misses (harmless re-expansions) stay rare (DESIGN.md §6.1).
    """
    return max(256, 8 * ef)


def _table_insert(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Insert (Q, R) ids into the (Q, H) open-addressed tables.

    Sequential over the R slots (R is small), vectorized over queries, so
    no two inserts race for the same empty slot.  An id whose probe window
    holds neither itself nor an empty slot is dropped — a capacity miss,
    surfacing later as a harmless re-expansion.  ids < 0 are skipped.
    """
    q, h = table.shape
    r = ids.shape[1]
    qrows = jnp.arange(q, dtype=jnp.int32)

    def body(rr, tab):
        v = jax.lax.dynamic_index_in_dim(ids, rr, axis=1, keepdims=False)
        pos = visited_probe_positions(v, h)               # (Q, PL)
        vals = tab[qrows[:, None], pos]                   # (Q, PL)
        found = jnp.any(vals == v[:, None], axis=-1)
        empty = vals == -1
        has_empty = jnp.any(empty, axis=-1)
        ins = pos[qrows, jnp.argmax(empty, axis=-1)]      # first empty probe
        do = (v >= 0) & ~found & has_empty
        return tab.at[qrows, ins].set(jnp.where(do, v, tab[qrows, ins]))

    return jax.lax.fori_loop(0, r, body, table)


def _table_member(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Membership of (Q, R) ids in the (Q, H) open-addressed tables.

    Exactly the fused kernel's visited probe (ref.search_expand_ref /
    kernels/search_expand.py): the shared `visited_probe_positions` window,
    any-slot id match.  Hoisted for callers that must probe OUTSIDE the
    kernel — the corpus-sharded search (core/corpus_shard.py), where the
    kernel sees shard-LOCAL row indices but the visited set is keyed by
    GLOBAL ids — with bitwise-identical results by the kernel/oracle
    parity contract.  Callers mask ids < 0 themselves (as the kernel's
    `ok` mask does); this probe alone may report them either way.
    """
    q, h = table.shape
    pos = visited_probe_positions(ids, h)                 # (Q, R, PL)
    qrows = jnp.arange(q, dtype=jnp.int32)[:, None, None]
    return jnp.any(table[qrows, pos] == ids[..., None], axis=-1)


@functools.partial(
    jax.jit,
    static_argnames=("k", "ef", "max_steps", "visited", "visited_cap",
                     "backend"))
def _search_impl(
    x,
    graph_ids: jnp.ndarray,
    queries: jnp.ndarray,
    entry: jnp.ndarray,
    valid: jnp.ndarray | None,
    rescore,
    vwords: jnp.ndarray | None,
    fwords: jnp.ndarray | None,
    ids_map: jnp.ndarray | None,
    *,
    k: int,
    ef: int,
    max_steps: int,
    visited: str,
    visited_cap: int,
    backend: str,
) -> SearchResult:
    # `backend` is unused in the body but part of the jit key: the kernels
    # dispatch on the global ops backend at TRACE time (same contract as
    # grnnd._build_graph_impl).
    del backend
    n, r = graph_ids.shape
    q = queries.shape[0]
    qrows = jnp.arange(q, dtype=jnp.int32)
    # trace-time flag, same idiom as the tombstone mask: the unfiltered
    # path compiles WITHOUT the predicate operands, the result heap, or
    # the extra per-step merge (tests/test_filtered.py jaxpr check)
    filtered = fwords is not None

    queries = queries.astype(jnp.float32)
    d_entry = ops.rowwise_sqdist(
        queries, jnp.broadcast_to(VS.take(x, entry), queries.shape))
    if valid is not None:
        # a dead entry contributes nothing; every later insertion into the
        # beam is already validity-filtered inside search_expand, so the
        # beam can never contain a tombstoned vertex
        d_entry = jnp.where(valid[entry], d_entry, jnp.inf)
    cand_ids = jnp.full((q, ef), -1, jnp.int32).at[:, 0].set(entry)
    cand_dists = jnp.full((q, ef), jnp.inf, jnp.float32).at[:, 0].set(d_entry)
    expanded = jnp.zeros((q, ef), bool)
    n_exp = jnp.zeros((q,), jnp.int32)

    if filtered:
        # result heap (route-through, DESIGN.md §9): the BEAM keeps every
        # live vertex so the walk can route through filtered-out regions;
        # only this separate heap — what the caller sees — applies the
        # predicate.  Seed it with the entry iff the entry itself passes.
        e_ok = jnp.any((vwords[entry][None, :] & fwords) != 0, axis=-1)
        e_ok = e_ok & jnp.isfinite(d_entry)
        res_ids = jnp.full((q, ef), -1, jnp.int32).at[:, 0].set(
            jnp.where(e_ok, entry, -1))
        res_dists = jnp.full((q, ef), jnp.inf, jnp.float32).at[:, 0].set(
            jnp.where(e_ok, d_entry, jnp.inf))

    entry_col = jnp.broadcast_to(entry, (q, 1)).astype(jnp.int32)
    if visited == "dense":
        vstate = jnp.zeros((q, n), bool).at[:, entry].set(True)
        # an empty 1-slot table turns the fused kernel's probe into a no-op
        lookup = jnp.full((q, 1), -1, jnp.int32)
    else:
        vstate = _table_insert(jnp.full((q, visited_cap), -1, jnp.int32),
                               entry_col)
        lookup = None

    def cond(state):
        frontier = (state[0] >= 0) & ~state[2]
        return (state[5] < max_steps) & jnp.any(frontier)

    def body(state):
        cand_ids, cand_dists, expanded, vstate, n_exp, steps = state[:6]
        frontier_d = jnp.where((cand_ids >= 0) & ~expanded, cand_dists, jnp.inf)
        sel = jnp.argmin(frontier_d, axis=-1)                      # (Q,)
        active = jnp.isfinite(jnp.min(frontier_d, axis=-1))        # (Q,)
        sel_id = cand_ids[qrows, sel]
        expanded = expanded.at[qrows, sel].set(True)

        nbrs = graph_ids[jnp.clip(sel_id, 0)]                      # (Q, R)
        nbrs = jnp.where(active[:, None] & (nbrs >= 0), nbrs, -1)

        # fused: gather neighbor vectors, query->neighbor distances, the
        # visited probe, the tombstone-validity probe, and (filtered) the
        # label-predicate test in one pass (dense mode probes the empty
        # dummy table and refines `fresh` with the exact bitmask below)
        out = ops.search_expand(
            x, queries, nbrs, vstate if lookup is None else lookup, valid,
            vwords if filtered else None, fwords if filtered else None)
        if filtered:
            nbrs, dq, fresh, allowed = out
        else:
            nbrs, dq, fresh = out
        if visited == "dense":
            seen = vstate[qrows[:, None], jnp.clip(nbrs, 0)]
            fresh = fresh & ~seen
            vstate = vstate.at[qrows[:, None], jnp.clip(nbrs, 0)].max(fresh)
        else:
            vstate = _table_insert(vstate, jnp.where(fresh, nbrs, -1))

        dq = jnp.where(fresh, dq, jnp.inf)
        n_exp = n_exp + jnp.sum(fresh, axis=-1, dtype=jnp.int32)

        # merge: keep ef best of (candidate list ∪ fresh neighbors) via the
        # deduplicating top-R primitive; candidates precede fresh entries,
        # so a re-entering duplicate keeps its original (possibly expanded)
        # beam slot.  Route-through: the beam takes fresh neighbors
        # REGARDLESS of the predicate — a filtered-out vertex must remain
        # a stepping stone to allowed ones beyond it.
        all_ids = jnp.concatenate([cand_ids, jnp.where(fresh, nbrs, -1)],
                                  axis=-1)
        all_d = jnp.concatenate([cand_dists, dq], axis=-1)
        new_ids, new_d = ops.topr_merge(all_ids, all_d, ef)

        # re-derive the expanded flags: an entry is expanded iff its id
        # matches a previously-expanded candidate slot (-2 sentinel keeps
        # empty slots from matching each other)
        exp_src = jnp.where(expanded & (cand_ids >= 0), cand_ids, -2)
        new_expanded = jnp.any(
            new_ids[:, :, None] == exp_src[:, None, :], axis=-1)
        new_expanded = new_expanded | (new_ids < 0)

        next_state = (new_ids, new_d, new_expanded, vstate, n_exp, steps + 1)
        if filtered:
            # a vertex enters the result heap exactly once — on its fresh
            # sighting, with its real distance, iff the predicate admits
            # it; re-sightings under hash-capacity misses are absorbed by
            # the merge dedup like everywhere else
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

    if rescore is not None:
        # fp32 rescoring pass (DESIGN.md §8.3): traversal ranked the beam
        # in the storage precision's distance space; re-rank the final ef
        # candidates with EXACT distances against the rescore tier.  One
        # (Q, ef, D) gather — ef·D bytes per query, tiny next to the
        # traversal traffic — then the usual dedup/sort merge primitive
        # (ids are already unique, so this is a pure re-sort).  Under a
        # filter this runs on the result heap, which holds ONLY allowed
        # ids — rescoring is restricted to the allowed set by construction.
        rv = VS.take(rescore, jnp.clip(out_ids, 0))            # (Q, ef, D)
        diff = queries[:, None, :] - rv
        d_exact = jnp.sum(diff * diff, axis=-1)
        d_exact = jnp.where(out_ids >= 0, d_exact, jnp.inf)
        out_ids, out_dists = ops.topr_merge(out_ids, d_exact, ef)

    out_ids, out_dists = out_ids[:, :k], out_dists[:, :k]
    if ids_map is not None:
        # optimized layout (core/layout.py): the graph rows are permuted;
        # one final gather converts internal row indices back to the
        # caller's original numbering.  Runs AFTER the k-slice and the
        # rescore re-rank, so everything upstream is untouched.
        out_ids = jnp.where(out_ids >= 0, ids_map[jnp.clip(out_ids, 0)], -1)
    return SearchResult(out_ids, out_dists, n_exp)


@functools.partial(jax.jit, static_argnames=("k",))
def _rescore_merge(out_ids, rv, queries, ids_map, *, k: int):
    """The re-rank half of the host-tier search (DESIGN.md §13).

    Identical math, line for line, to the in-loop rescore tail of
    `_search_impl`: exact fp32 distances against the gathered rows, pad
    slots masked to +inf BY ID (so the gathered content of a pad row is
    irrelevant — the host gather ships zeros for them), the same
    `topr_merge` re-sort, the same k-slice-then-ids_map order.  Running
    it as a second jitted program instead of inside the traversal
    program cannot change a bit: every op is the same jnp formula on the
    same operands (the corpus-shard tier relies on the identical
    same-formula-across-programs contract).
    """
    ef = out_ids.shape[1]
    diff = queries[:, None, :] - rv
    d_exact = jnp.sum(diff * diff, axis=-1)
    d_exact = jnp.where(out_ids >= 0, d_exact, jnp.inf)
    out_ids, out_dists = ops.topr_merge(out_ids, d_exact, ef)
    out_ids, out_dists = out_ids[:, :k], out_dists[:, :k]
    if ids_map is not None:
        out_ids = jnp.where(out_ids >= 0, ids_map[jnp.clip(out_ids, 0)], -1)
    return out_ids, out_dists


def search(
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
    overfetch: int = 4,
    ids_map: jnp.ndarray | None = None,
) -> SearchResult:
    """Search the graph for the k nearest vertices to each query row.

    `x` is the traversal-tier dataset: a plain fp32 array or a
    `core.vecstore.VectorStore` (bf16 / int8 per the precision ladder,
    DESIGN.md §8) — the fused expansion kernel dequantizes rows on the fly.

    `visited` selects the visited-set representation: "dense" (exact (Q, N)
    bitmask) or "hashed" (per-query `visited_cap`-slot open-addressed table,
    O(Q·H) memory independent of N — the serving configuration at scale).
    `visited_cap` defaults to `default_visited_cap(ef)`.

    `valid` is the dynamic index's (N,) vertex-validity mask (tombstoned or
    not-yet-allocated rows are False, core/dynamic.py): dead vertices are
    excluded from traversal entirely — never expanded, scored, or returned
    — so the result set is exactly what a search over the physically
    compacted graph would produce.  None (the static-index default) keeps
    the original path bit-for-bit.

    `rescore` is the optional exact tier for quantized traversal (the
    CAGRA/GGNN two-tier layout): an (N, D) fp32 array (or higher-precision
    store) from which the final ef candidates are re-ranked with exact
    distances.  None (the default) returns traversal-space distances
    unchanged — the fp32 path stays bit-for-bit.  A `vecstore.HostTier`
    selects the HOST-COLD placement (DESIGN.md §13): traversal runs
    device-side without the rescore operand, the final ef candidate ids
    cross to the host, ef·D fp32 bytes come back (pad slots excluded from
    the transfer), and `_rescore_merge` re-ranks with the identical math
    — bitwise-equal to the device-resident tier (tests/test_tiered.py).

    `labels`/`filter` select FILTERED search (core/labels.py, DESIGN.md
    §9): `labels` is a `LabelStore` (or raw (N, W) packed vertex words)
    and `filter` the per-query predicate — (Q, W) packed allowed words, a
    (Q, L) boolean label mask, or (Q,) single allowed label ids.  The
    traversal ROUTES THROUGH filtered-out vertices (they stay in the beam
    with their real distances, preserving graph connectivity under
    masking) while a separate result heap admits only predicate-passing
    vertices — every returned id satisfies its query's predicate, a hard
    invariant.  `overfetch` widens the working ef to at least
    `overfetch * k` under a filter so k allowed survivors remain at
    moderate selectivity; at LOW selectivity callers should additionally
    raise `ef` toward ~k/selectivity (the over-fetch policy, DESIGN.md
    §9.3).  None (the default) keeps the unfiltered path bit-for-bit —
    the predicate operands are absent from the compiled program entirely.

    `ids_map` is the optimized-layout inverse permutation (core/layout.py):
    an (N,) int32 map applied to the returned ids in one final gather, so
    an index whose rows were renumbered for locality still reports ids in
    the caller's original numbering.  None (the default) keeps the
    unmapped path bit-for-bit (the gather is absent from the trace).
    """
    assert ef >= k
    assert visited in ("dense", "hashed"), visited
    assert visited_cap is None or visited_cap > 0, visited_cap
    if filter is not None:
        assert labels is not None, "filtered search needs a label store"
        vwords = L.store_words(labels)
        fwords = L.query_words(filter, vwords.shape[1])
        ef = max(ef, overfetch * k)
    else:
        vwords = fwords = None  # labels alone is inert (no predicate given)
    if entry is None:
        entry = medoid(x, valid)
    queries, fwords, qn = align_queries(queries, fwords)
    if visited == "dense":
        cap = 0  # unused; normalized so it never fragments the jit cache
    else:
        cap = visited_cap if visited_cap is not None else default_visited_cap(ef)
    if VS.is_host(rescore):
        # host-cold tier: traversal compiles WITHOUT the rescore operand
        # (k=ef keeps the full beam/heap — the k-slice is deferred to the
        # merge program), the gather crosses the boundary in host numpy,
        # and the re-rank runs as its own jitted program.  ids_map is
        # also deferred so the host gather indexes internal row numbers.
        res = _search_impl(x, graph_ids, queries, entry, valid, None,
                           vwords, fwords, None,
                           k=ef, ef=ef, max_steps=max_steps,
                           visited=visited, visited_cap=cap,
                           backend=ops.effective_backend())
        rv = rescore.gather(res.ids)                       # (Q, ef, D)
        out_ids, out_dists = _rescore_merge(
            res.ids, rv, jnp.asarray(queries, jnp.float32), ids_map, k=k)
        return unpad(SearchResult(out_ids, out_dists, res.n_expanded), qn)
    return unpad(_search_impl(x, graph_ids, queries, entry, valid, rescore,
                              vwords, fwords, ids_map,
                              k=k, ef=ef, max_steps=max_steps,
                              visited=visited, visited_cap=cap,
                              backend=ops.effective_backend()), qn)
