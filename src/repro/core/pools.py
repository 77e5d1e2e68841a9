"""Fixed-capacity, double-buffered neighbor pools (GRNND §3.5) — functional.

A pool is a pair of arrays over all N vertices:

    pool_ids   (N, R) int32    — neighbor vertex ids, -1 marks an empty slot
    pool_dists (N, R) float32  — squared L2 distance to the owning vertex,
                                 +inf marks an empty slot

The GPU version holds two static R-slot buffers per vertex and swaps
pointers; here the double buffer is value semantics (the update produces new
arrays) and the "clear" is re-initialization to sentinels.  The GPU's atomic
WARP_INSERT becomes a deterministic two-stage dataflow:

  1. group_requests: all (dst, src, dist) insertion requests of a round are
     lex-sorted (dst-major, dist-minor), capacity-capped per destination
     segment, and scattered into a per-vertex staging buffer — this replaces
     inter-warp atomics with one sort + one scatter;
  2. topr_merge: per vertex, pool ∪ staging is deduped and the R closest
     survive — this replaces ballot dedup + replace-farthest-if-closer.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops


class Pool(NamedTuple):
    ids: jnp.ndarray    # (N, R) int32
    dists: jnp.ndarray  # (N, R) float32

    @property
    def n(self) -> int:
        return self.ids.shape[0]

    @property
    def r(self) -> int:
        return self.ids.shape[1]

    def degree(self) -> jnp.ndarray:
        return jnp.sum(self.ids >= 0, axis=-1)


def empty_pool(n: int, r: int) -> Pool:
    return Pool(
        ids=jnp.full((n, r), -1, jnp.int32),
        dists=jnp.full((n, r), jnp.inf, jnp.float32),
    )


def init_random(key: jax.Array, x, s: int, r: int) -> Pool:
    """Random S-NN initialization (paper Alg. 3 lines 3-5).

    Each vertex receives S distinct-ish random neighbors (self-edges are
    rerolled by offset), with true distances, placed in an R-capacity pool.
    `x` may be a VectorStore (the precision ladder): init distances are
    then computed in the same storage-precision distance space as every
    later round, so the pool's distance invariants stay consistent.
    """
    n, _ = x.shape
    assert s <= r
    raw = jax.random.randint(key, (n, s), 0, n - 1, jnp.int32)
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    # map the range [0, n-1) onto [0, n) \ {v}: anything >= v shifts up by 1
    ids = jnp.where(raw >= rows, raw + 1, raw)
    dists = _owner_dists(x, rows[:, 0], ids)
    ids = jnp.pad(ids, ((0, 0), (0, r - s)), constant_values=-1)
    dists = jnp.pad(dists, ((0, 0), (0, r - s)), constant_values=jnp.inf)
    # dedup (randint can repeat) + sort by distance
    return Pool(*ops.topr_merge(ids, dists, r))


def _owner_dists(x, owners: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """d(x[owner], x[id]) for an (B, K) id matrix; invalid ids -> +inf.

    One fused paired-distance call (`ops.gather_sqdist`): rows are read
    at storage precision and dequantized in the kernel, the same values
    the fused build kernels see, and no (B·K, D) gathered copy exists —
    at N = 1M, S = 24 that copy alone would be 12 GB.
    """
    b, k = ids.shape
    d = ops.gather_sqdist(x, jnp.repeat(owners, k),
                          jnp.clip(ids, 0).reshape(-1)).reshape(b, k)
    return jnp.where(ids >= 0, d, jnp.inf)


class Requests(NamedTuple):
    """A flat batch of insertion requests: put `src` into `dst`'s pool."""
    dst: jnp.ndarray   # (M,) int32, -1 = inactive
    src: jnp.ndarray   # (M,) int32
    dist: jnp.ndarray  # (M,) float32  d(dst, src)


def concat_requests(*reqs: Requests) -> Requests:
    return Requests(
        dst=jnp.concatenate([r.dst for r in reqs]),
        src=jnp.concatenate([r.src for r in reqs]),
        dist=jnp.concatenate([r.dist for r in reqs]),
    )


def group_requests(req: Requests, n: int, cap: int,
                   drop_self: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stage a flat Requests batch into per-destination buffers.

    `drop_self=False` skips the dst == src self-insert filter — for the
    distributed paths, whose destinations are RE-BASED to shard-local row
    indices while sources stay global: comparing those spaces would both
    miss true self-inserts and drop genuine cross-space coincidences, so
    the self filter runs in global space (`distributed._filter_to_local`)
    before re-basing instead.
    """
    return _stage(req.dst, req.src, req.dist, n, cap, drop_self=drop_self)


def stage_request_matrix(
    dst: jnp.ndarray, src: jnp.ndarray, dist: jnp.ndarray, n: int, cap: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stage the fused round's (N, P) request matrices: -> ids/dists (N, cap).

    This is the direct consumer of `ops.rng_propagation_round` output —
    the row-major flatten below is a metadata-only reshape, so no (N·P,)
    request copies (and no Requests tuple) are materialized between the
    kernel and the sort/scatter staging pipeline.
    """
    return _stage(dst.reshape(-1), src.reshape(-1), dist.reshape(-1), n, cap)


def _stage(dst, src_in, dist_in, n: int, cap: int,
           drop_self: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stage requests into per-destination buffers: -> ids/dists (N, cap).

    Deterministic replacement for atomic concurrent insertion: repeated
    (dst, src) requests are dropped, the rest ordered dst-major /
    dist-minor, and each destination takes the first `cap` entries of its
    run.  Self-inserts (dst == src; only meaningful when both live in the
    same id space — see group_requests) and inactive requests are dropped.
    Which copy of a repeated request survives, and the order among equal
    distances, are fixed by the sort but not specified.

    Built for the TPU compiler, whose time grows with the length of 1-D
    scans and scatters and with the keys and operands of a sort (at
    M = 12M: an associative_scan did not compile in 4 minutes, a scatter
    took 20 s, a sort on an f32 key with a stable tie-break 157 s, the
    same sort on int32 keys about a third of that): two 2-key int32
    sorts carrying one payload each, a binary search per destination for
    its run, and (N, cap) gathers.
    """
    if drop_self:
        dst = jnp.where(dst == src_in, -1, dst)
    m = dst.shape[0]
    if m == 0:
        return (jnp.full((n, cap), -1, jnp.int32),
                jnp.full((n, cap), jnp.inf, jnp.float32))
    dst_key = jnp.where(dst >= 0, dst, n)  # inactive sorts to the end

    # dedup identical (dst, src) requests so duplicates cannot crowd out
    # distinct candidates at the capacity rank below: order by (dst, src),
    # retire every repeat after the first
    dst_p, src_p, idx_p = jax.lax.sort(
        (dst_key, src_in, jnp.arange(m, dtype=jnp.int32)), num_keys=2)
    dup = jnp.concatenate([
        jnp.array([False]),
        (dst_p[1:] == dst_p[:-1]) & (src_p[1:] == src_p[:-1]) & (dst_p[1:] < n),
    ])
    dst_p = jnp.where(dup, n, dst_p)
    dist_p = jnp.where(dst_p < n, dist_in[idx_p], jnp.inf)

    # dst-major, dist-minor; a non-negative float's bits order like it
    # (+ 0.0 folds a -0.0 into +0.0)
    dist_bits = jax.lax.bitcast_convert_type(dist_p + 0.0, jnp.int32)
    dst_s, bits_s, src_s = jax.lax.sort((dst_p, dist_bits, src_p),
                                        num_keys=2)
    dist_s = jax.lax.bitcast_convert_type(bits_s, jnp.float32)
    # destination v's run is [start[v], start[v + 1]); take its first cap
    start = jnp.searchsorted(dst_s, jnp.arange(n + 1, dtype=dst_s.dtype),
                             side="left", method="scan").astype(jnp.int32)
    pos = start[:n, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    ok = pos < start[1:, None]
    pos = jnp.minimum(pos, m - 1)
    staged_ids = jnp.where(ok, src_s[pos], -1)
    staged_dists = jnp.where(ok, dist_s[pos], jnp.inf)
    return staged_ids, staged_dists


def merge_into(pool: Pool, cand_ids: jnp.ndarray, cand_dists: jnp.ndarray) -> Pool:
    """pool ∪ candidates -> R closest unique (the WARP_INSERT analogue)."""
    ids = jnp.concatenate([pool.ids, cand_ids], axis=-1)
    dists = jnp.concatenate([pool.dists, cand_dists], axis=-1)
    return Pool(*ops.topr_merge(ids, dists, pool.r))


def insert_requests(pool: Pool, req: Requests, cap: int | None = None) -> Pool:
    """Group a request batch and merge it into the pool (both stages)."""
    cap = cap if cap is not None else pool.r
    staged_ids, staged_dists = group_requests(req, pool.n, cap)
    return merge_into(pool, staged_ids, staged_dists)


def build_requests_into_empty(
    n: int, r: int, req: Requests, cap: int | None = None
) -> Pool:
    """Materialize a fresh pool (the cleared write buffer) from requests only."""
    cap = cap if cap is not None else r
    staged_ids, staged_dists = group_requests(req, n, max(cap, r))
    return Pool(*ops.topr_merge(staged_ids, staged_dists, r))
