"""GRNND: GPU-parallel Relative NN-Descent, adapted to TPU/JAX.

Implements paper Alg. 3/4 as a fully batched, functional pipeline:

  * disordered neighbor propagation (§3.3): every vertex samples
    `pairs_per_vertex` random slot pairs from its read buffer, applies the
    RNG criterion d(n_i, n_j) < max(d(v, n_i), d(v, n_j)) and redirects the
    farther endpoint into the closer endpoint's write buffer;
  * ascending / descending sorted rounds (§4.3 ablation, Fig. 2b/7): the
    faithful parallel port of the sequential UPDATE_NEIGHBORS (Alg. 2) —
    candidates evaluated against already-accepted neighbors in sorted order;
  * the double-buffered pool (§3.5): each round builds the write buffer from
    scratch out of (redirect ∪ survivor) requests, then the buffers swap —
    in functional form, the new Pool value replaces the old;
  * reverse edge sampling (§3.6): between outer iterations, each vertex
    requests insertion of itself into its top ρ·k neighbors' pools.

Batched-vs-sequential semantics note (recorded in DESIGN.md): within one
round all pair evaluations see the same read-buffer snapshot, so a slot
killed by one pair is still visible to other pairs of the same round; kills
are OR-combined at the end of the round.  The GPU version interleaves these
within a warp; both are stochastic explorations of the same criterion and
converge to graphs of equal recall (validated in tests/benchmarks).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import pools as P
from repro.core import vecstore as VS
from repro.kernels import ops


class GRNNDConfig(NamedTuple):
    s: int = 16                    # initial random neighbors per vertex
    r: int = 32                    # pool capacity (R)
    t1: int = 3                    # outer iterations (T1)
    t2: int = 4                    # inner rounds (T2)
    rho: float = 0.6               # reverse-edge sampling ratio (ρ)
    pairs_per_vertex: int = 32     # sampled candidate pairs per round
    order: str = "disordered"      # "disordered" | "ascending" | "descending"
    incoming_cap: int | None = None  # staged insertions per vertex per round
    chunk_size: int | None = None    # vertex chunking for bounded memory

    @property
    def cap(self) -> int:
        return self.incoming_cap if self.incoming_cap is not None else self.r


# ---------------------------------------------------------------------------
# Disordered propagation round (Alg. 4)
# ---------------------------------------------------------------------------

def _sample_slot_pairs(key, c, r, p):
    """The shared pair sampling: drawn outside the kernel so every backend
    (pallas / interpret / ref) evaluates the identical pairs."""
    ki, kj = jax.random.split(key)
    si = jax.random.randint(ki, (c, p), 0, r, jnp.int32)
    sj = jax.random.randint(kj, (c, p), 0, r, jnp.int32)
    return si, sj


def _pair_matrices_chunk(x, ids_c, dists_c, key, cfg: GRNNDConfig):
    """Fused pair evaluation for a chunk: (dst, src, dij) (C, P) + kill (C, R).

    The gather -> rowwise_sqdist -> scatter pipeline this used to lower to
    is now one fused op (kernels/rng_round.py): neighbor vectors are pulled
    into VMEM once per vertex, pair distances and the RNG criterion (paper
    eq. 2) are evaluated in-register, and the redirect requests plus kill
    mask come out in a single pass.
    """
    c, r = ids_c.shape
    si, sj = _sample_slot_pairs(key, c, r, cfg.pairs_per_vertex)
    return ops.rng_propagation_round(x, ids_c, dists_c, si, sj)


def _pair_requests_chunk(x, ids_c, dists_c, rows_c, key, cfg: GRNNDConfig):
    """Request-tuple adapter over the fused round (distributed build entry).

    Returns (redirect Requests, kill mask (C, R) bool).
    """
    del rows_c
    dst, src, dij, killed = _pair_matrices_chunk(x, ids_c, dists_c, key, cfg)
    redirect = P.Requests(
        dst=dst.reshape(-1), src=src.reshape(-1), dist=dij.reshape(-1))
    return redirect, killed


# ---------------------------------------------------------------------------
# Sorted round (faithful parallel Alg. 2 — the ascending/descending ablation)
# ---------------------------------------------------------------------------

def _sorted_requests_chunk(x, ids_c, dists_c, rows_c, key, cfg: GRNNDConfig):
    """Alg. 2 applied per vertex on a snapshot, vectorized over the chunk.

    Candidates are processed in ascending (or descending) distance order;
    each is compared against all previously *accepted* neighbors; a conflict
    (d(n, n') <= d(v, n)) rejects the candidate and redirects it to the first
    accepted conflictor.  Returns (redirect Requests, kill mask (C, R)).
    """
    del key
    c, r = ids_c.shape
    sign = 1.0 if cfg.order == "ascending" else -1.0
    order = jnp.argsort(jnp.where(ids_c >= 0, sign * dists_c, jnp.inf), axis=-1)
    ids_o = jnp.take_along_axis(ids_c, order, axis=-1)
    dv_o = jnp.take_along_axis(dists_c, order, axis=-1)
    valid_o = ids_o >= 0

    # pairwise distances among pool members, in sorted-slot space
    # (store-aware gather: rows land dequantized fp32, the same values the
    # fused disordered-round kernel dequantizes in VMEM)
    vecs = VS.take(x, jnp.clip(ids_o, 0).reshape(-1)).reshape(c, r, -1)
    xx = jnp.sum(vecs * vecs, axis=-1)
    g = xx[:, :, None] + xx[:, None, :] - 2.0 * jnp.einsum(
        "crd,csd->crs", vecs, vecs, preferred_element_type=jnp.float32)
    g = jnp.maximum(g, 0.0)

    def step(accepted, i):
        g_i = jax.lax.dynamic_index_in_dim(g, i, axis=1, keepdims=False)  # (C,R)
        dv_i = jax.lax.dynamic_index_in_dim(dv_o, i, axis=1, keepdims=False)
        ok_i = jax.lax.dynamic_index_in_dim(valid_o, i, axis=1, keepdims=False)
        conflict = accepted & (g_i <= dv_i[:, None])                      # (C,R)
        any_conflict = jnp.any(conflict, axis=-1)
        accept_i = ok_i & ~any_conflict
        accepted = accepted.at[:, i].set(accept_i)
        # first accepted conflictor in processing order
        slot_rank = jnp.where(conflict, jnp.arange(r, dtype=jnp.int32)[None, :], r)
        j = jnp.min(slot_rank, axis=-1)                                   # (C,)
        red_dst = jnp.where(
            ok_i & any_conflict,
            jnp.take_along_axis(ids_o, jnp.clip(j, 0, r - 1)[:, None], 1)[:, 0],
            -1,
        )
        red_d = jnp.take_along_axis(
            g_i, jnp.clip(j, 0, r - 1)[:, None], axis=1)[:, 0]
        src_i = jnp.take_along_axis(ids_o, jnp.full((c, 1), i, jnp.int32), 1)[:, 0]
        return accepted, (red_dst, src_i, red_d, accept_i)

    accepted0 = jnp.zeros((c, r), bool)
    accepted, (red_dst, red_src, red_d, accept_seq) = jax.lax.scan(
        step, accepted0, jnp.arange(r, dtype=jnp.int32))

    redirect = P.Requests(
        dst=red_dst.T.reshape(-1),   # scan stacks on axis 0 -> (R, C)
        src=red_src.T.reshape(-1),
        dist=red_d.T.reshape(-1),
    )
    # kill = evaluated-and-rejected slots, mapped back to original slot space
    accepted_orig = jnp.zeros((c, r), bool)
    accepted_orig = accepted_orig.at[
        jnp.broadcast_to(jnp.arange(c)[:, None], (c, r)), order
    ].set(accepted)
    killed = (ids_c >= 0) & ~accepted_orig
    return redirect, killed


# ---------------------------------------------------------------------------
# One inner round: requests -> fresh write buffer -> swap
# ---------------------------------------------------------------------------

def _chunked(pool: P.Pool, key, cfg: GRNNDConfig):
    """Yield the (ids, dists, key) chunking plan, or None for one-shot.

    A ragged last chunk (n % chunk != 0) is padded with empty vertices
    (ids -1, dists +inf): they sample no valid pair, so they issue no
    request and kill nothing, and callers slice them off at row n.
    """
    n, r = pool.ids.shape
    chunk = cfg.chunk_size
    if chunk is None or chunk >= n:
        return None
    n_chunks = -(-n // chunk)
    pad = ((0, n_chunks * chunk - n), (0, 0))
    return (jnp.pad(pool.ids, pad, constant_values=-1)
            .reshape(n_chunks, chunk, r),
            jnp.pad(pool.dists, pad, constant_values=jnp.inf)
            .reshape(n_chunks, chunk, r),
            jax.random.split(key, n_chunks))


def _round_pair_matrices(x, pool: P.Pool, key, cfg: GRNNDConfig):
    """Disordered round over all vertices: fused (N, P) matrices + kill."""
    n, r = pool.ids.shape
    plan = _chunked(pool, key, cfg)
    if plan is None:
        return _pair_matrices_chunk(x, pool.ids, pool.dists, key, cfg)

    ids_ch, dists_ch, keys = plan
    dst, src, dij, killed = jax.lax.map(
        lambda a: _pair_matrices_chunk(x, a[0], a[1], a[2], cfg),
        (ids_ch, dists_ch, keys))
    p = dst.shape[-1]
    return (dst.reshape(-1, p)[:n], src.reshape(-1, p)[:n],
            dij.reshape(-1, p)[:n], killed.reshape(-1, r)[:n])


def _round_requests(x, pool: P.Pool, key, cfg: GRNNDConfig):
    """Sorted-order round (ascending/descending ablation): flat Requests."""
    n, r = pool.ids.shape
    plan = _chunked(pool, key, cfg)
    if plan is None:
        rows = jnp.arange(n, dtype=jnp.int32)
        return _sorted_requests_chunk(x, pool.ids, pool.dists, rows, key, cfg)

    ids_ch, dists_ch, keys = plan
    n_chunks, chunk = ids_ch.shape[:2]
    rows_ch = jnp.arange(n_chunks * chunk, dtype=jnp.int32).reshape(-1, chunk)
    red, killed = jax.lax.map(
        lambda a: _sorted_requests_chunk(x, a[0], a[1], a[2], a[3], cfg),
        (ids_ch, dists_ch, rows_ch, keys))
    # pad rows are empty, so their requests are all inactive (dst -1)
    redirect = P.Requests(
        dst=red.dst.reshape(-1), src=red.src.reshape(-1),
        dist=red.dist.reshape(-1))
    return redirect, killed.reshape(-1, r)[:n]


def _update_requests(x, pool: P.Pool, key, cfg: GRNNDConfig):
    """One round's redirects as (N, W) dst/src/dist matrices + the (N, R)
    kill mask.  The disordered path passes the fused kernel's (N, P)
    matrices straight through; the sorted ablations' flat requests are
    vertex-major, so they fold to (N, R) rows (a chunk plan's pad rows
    come last and are all inactive)."""
    n, r = pool.ids.shape
    if cfg.order == "disordered":
        return _round_pair_matrices(x, pool, key, cfg)
    redirect, killed = _round_requests(x, pool, key, cfg)

    def rows(a):
        return a.reshape(-1, r)[:n]

    return rows(redirect.dst), rows(redirect.src), rows(redirect.dist), killed


def _apply_requests(pool: P.Pool, dst, src, dist, killed,
                    cfg: GRNNDConfig) -> P.Pool:
    """Stage (N, W) requests and merge them with the surviving slots."""
    n = pool.ids.shape[0]
    staged_i, staged_d = P.stage_request_matrix(dst, src, dist, n, cfg.cap)
    if killed is not None:
        pool = P.Pool(jnp.where(killed, -1, pool.ids),
                      jnp.where(killed, jnp.inf, pool.dists))
    return P.merge_into(pool, staged_i, staged_d)


def update_round(x, pool: P.Pool, key, cfg: GRNNDConfig) -> P.Pool:
    """One UPDATE_NEIGHBORS_PARALLEL round incl. buffer swap (Alg. 4).

    Perf iteration g1 (EXPERIMENTS.md §Perf): survivors (Alg. 4 lines
    11-15) are already per-vertex aligned, so they bypass the request
    sort/scatter entirely — only cross-vertex redirects are grouped.  The
    merged result is the identical top-R of the same union.

    The disordered path consumes the fused kernel's (N, P) matrices
    directly (pools.stage_request_matrix) — no flat (N·P,) Requests
    intermediate.
    """
    return _apply_requests(pool, *_update_requests(x, pool, key, cfg), cfg)


# ---------------------------------------------------------------------------
# Reverse edge sampling (§3.6)
# ---------------------------------------------------------------------------

def _reverse_requests(pool: P.Pool, rho):
    """(N, R) requests inserting v into its top ρ·k neighbors' pools."""
    n, r = pool.ids.shape
    rows = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, r))
    deg = pool.degree()[:, None]                                  # (N, 1)
    take = jnp.ceil(rho * deg).astype(jnp.int32)
    slot = jnp.broadcast_to(jnp.arange(r, dtype=jnp.int32)[None, :], (n, r))
    sel = (slot < take) & (pool.ids >= 0)
    return (jnp.where(sel, pool.ids, -1),   # insert INTO neighbor
            rows,                           # ... the owner vertex
            pool.dists)                     # d symmetric


def reverse_edge_round(pool: P.Pool, cfg: GRNNDConfig, rho=None) -> P.Pool:
    """Insert v into the pools of its top ρ·k neighbors (k = live degree).

    Pools are distance-sorted (topr_merge invariant), so "top ρ·k" is a
    per-row prefix of ceil(ρ · degree) slots.
    """
    rho = cfg.rho if rho is None else rho
    return _apply_requests(pool, *_reverse_requests(pool, rho), None, cfg)


# ---------------------------------------------------------------------------
# Full build (Alg. 3)
# ---------------------------------------------------------------------------

def _pad_cols(w, *mats):
    """Pad (N, k) request matrices to width w with inactive requests."""
    fills = (-1, -1, jnp.inf)
    return tuple(jnp.pad(a, ((0, 0), (0, w - a.shape[1])), constant_values=f)
                 for a, f in zip(mats, fills))


@functools.partial(jax.jit, static_argnames=("cfg", "backend"))
def _build_graph_impl(key: jax.Array, x: jnp.ndarray, cfg: GRNNDConfig,
                      t1, t2, rho, backend: str = "auto") -> P.Pool:
    """t1/t2/rho are traced: hyperparameter sweeps share one compilation.

    `backend` is unused in the body but part of the jit key: the kernels
    dispatch on the global ops backend at TRACE time, so without it a
    cached executable from one backend would silently serve another.

    The T1 blocks of T2 update rounds, with a reverse-edge round between
    blocks, run as ONE loop whose body picks the round's requests and
    then stages and merges them: the program holds a single copy of the
    request staging (two sorts of N·P elements), which dominates its
    compile time on a TPU.
    """
    del backend
    k_init, k_rounds = jax.random.split(key)
    pool = P.init_random(k_init, x, cfg.s, cfg.r)
    n, r = pool.ids.shape
    w = max(r, cfg.pairs_per_vertex if cfg.order == "disordered" else r)

    def update(s, pool):
        t1_i, t2_i = s // (t2 + 1), s % (t2 + 1)
        k = jax.random.fold_in(jax.random.fold_in(k_rounds, t1_i), t2_i)
        dst, src, dist, killed = _update_requests(x, pool, k, cfg)
        return _pad_cols(w, dst, src, dist) + (killed,)

    def reverse(s, pool):
        return (_pad_cols(w, *_reverse_requests(pool, rho))
                + (jnp.zeros((n, r), bool),))

    def step(s, pool):
        reqs = jax.lax.cond(s % (t2 + 1) == t2, reverse, update, s, pool)
        return _apply_requests(pool, *reqs, cfg)

    # step s is update round s % (T2+1) of block s // (T2+1), or (at
    # s % (T2+1) == T2) the reverse round after that block; the last
    # block has none
    return jax.lax.fori_loop(0, t1 * (t2 + 1) - 1, step, pool)


def build_graph(key: jax.Array, x, cfg: GRNNDConfig) -> P.Pool:
    """Construct the ANN graph: init -> T1 x (T2 rounds + reverse sampling).

    `x` is a plain fp32 array or a `core.vecstore.VectorStore` (bf16/int8
    per the precision ladder, DESIGN.md §8): every distance of the build —
    init, fused propagation rounds, sorted ablations — is then computed on
    storage-precision rows (dequantized in-kernel), with fp32 accumulation
    as always.
    """
    static_cfg = cfg._replace(t1=-1, t2=-1, rho=-1.0)  # normalize jit key
    return _build_graph_impl(key, x, static_cfg,
                             jnp.int32(cfg.t1), jnp.int32(cfg.t2),
                             jnp.float32(cfg.rho),
                             backend=ops.effective_backend())


def build_graph_with_stats(key, x, cfg: GRNNDConfig):
    """Un-jitted build that also returns per-round degree/change diagnostics."""
    n = x.shape[0]
    k_init, k_rounds = jax.random.split(key)
    pool = P.init_random(k_init, x, cfg.s, cfg.r)
    stats = []
    for t1 in range(cfg.t1):
        for t2 in range(cfg.t2):
            k = jax.random.fold_in(jax.random.fold_in(k_rounds, t1), t2)
            new_pool = update_round(x, pool, k, cfg)
            changed = jnp.mean((new_pool.ids != pool.ids).astype(jnp.float32))
            stats.append({
                "t1": t1, "t2": t2,
                "mean_degree": float(jnp.mean(new_pool.degree())),
                "frac_changed": float(changed),
            })
            pool = new_pool
        if t1 != cfg.t1 - 1:
            pool = reverse_edge_round(pool, cfg)
    return pool, stats
