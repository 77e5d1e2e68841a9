"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (interpret=True
on CPU, real lowering on TPU) and the fallback implementation used when the
Pallas path is disabled (e.g. CPU benchmarking, where interpret mode would be
orders of magnitude slower than XLA:CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "pairwise_sqdist_ref",
    "rowwise_sqdist_ref",
    "topr_merge_ref",
    "rng_round_ref",
    "search_expand_ref",
    "gather_sqdist_ref",
    "dequant_rows",
    "visited_probe_positions",
    "HASH_PROBES",
]

# Linear-probe window of the open-addressed visited table (DESIGN.md §6.1);
# the single source shared by the oracle, the Pallas kernel, and the
# table-insert path in core/search.py.
HASH_PROBES = 8


def dequant_rows(data: jnp.ndarray, scale, offset) -> jnp.ndarray:
    """The precision ladder's dequant (DESIGN.md §8): fp32 widen, then the
    per-dim affine correction.  scale/offset None = a float rung (fp32 or
    bf16 storage), where the widen alone is exact.

    This is the single formula shared by `core.vecstore.VectorStore`, every
    oracle below, and — inlined operation-for-operation — the Pallas kernel
    bodies: it is elementwise, so oracle and kernel produce bitwise-equal
    fp32 rows from the same stored bytes (tests/test_precision.py).
    """
    x = data.astype(jnp.float32)
    if scale is not None:
        x = x * scale + offset
    return x


def pairwise_sqdist_ref(
    x: jnp.ndarray,
    y: jnp.ndarray,
    x_scale=None, x_offset=None,
    y_scale=None, y_offset=None,
) -> jnp.ndarray:
    """Squared L2 distances between all rows of x (M,D) and y (N,D) -> (M,N).

    Uses the MXU-friendly decomposition ||x-y||^2 = ||x||^2 + ||y||^2 - 2 x.y
    with fp32 accumulation, clamped at zero (the decomposition can go slightly
    negative in floating point).  The optional per-side (D,) scale/offset are
    the precision ladder's fused dequant (applied to the stored rows before
    the distance math — see `dequant_rows`).
    """
    x = dequant_rows(x, x_scale, x_offset)
    y = dequant_rows(y, y_scale, y_offset)
    xx = jnp.sum(x * x, axis=-1, keepdims=True)  # (M, 1)
    yy = jnp.sum(y * y, axis=-1)[None, :]        # (1, N)
    # HIGHEST: on a TPU the default f32 matmul rounds its inputs to bf16,
    # which would make the oracle (and brute-force ground truth) inexact
    xy = jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)  # (M, N)
    return jnp.maximum(xx + yy - 2.0 * xy, 0.0)


def rowwise_sqdist_ref(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Squared L2 distance between corresponding rows of x and y: (M,D)x(M,D)->(M,)."""
    d = x.astype(jnp.float32) - y.astype(jnp.float32)
    return jnp.sum(d * d, axis=-1)


def gather_sqdist_ref(
    x: jnp.ndarray,
    ni: jnp.ndarray,
    nj: jnp.ndarray,
    scale=None, offset=None,
) -> jnp.ndarray:
    """d(x[ni[m]], x[nj[m]]) for m in [0, M) — oracle for gather_l2.py.

    Indices < 0 are clamped to row 0 (matching the kernel's clamp; callers
    mask invalid entries themselves).  scale/offset are the precision
    ladder's per-dim dequant of the stored x rows.
    """
    n = x.shape[0]
    xi = dequant_rows(x[jnp.clip(ni, 0, n - 1)], scale, offset)
    xj = dequant_rows(x[jnp.clip(nj, 0, n - 1)], scale, offset)
    d = xi - xj
    return jnp.sum(d * d, axis=-1)


def rng_round_ref(
    x: jnp.ndarray,
    ids: jnp.ndarray,
    dists: jnp.ndarray,
    si: jnp.ndarray,
    sj: jnp.ndarray,
    scale=None, offset=None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One disordered RNG propagation round (GRNND Alg. 4 lines 4-10).

    Args:
      x:     (N, D) dataset (fp32/bf16/int8 per the precision ladder).
      ids:   (C, R) int32 pool ids; -1 marks an empty slot.
      dists: (C, R) float32 distances to the owning vertex; +inf for empty.
      si/sj: (C, P) int32 sampled slot indices in [0, R) — drawn by the
             caller so every backend evaluates the identical pairs.
      scale/offset: optional (D,) per-dim dequant of the stored x rows
             (`dequant_rows`); None = float storage.

    Returns (dst (C,P) i32, src (C,P) i32, dij (C,P) f32, kill (C,R) bool).
    For each sampled pair that is valid (both slots occupied, distinct
    neighbors) and passes the RNG criterion d(n_i, n_j) < max(d(v, n_i),
    d(v, n_j)), the farther endpoint `src` is redirected into the closer
    endpoint `dst`'s pool and the farther endpoint's slot is killed;
    missed pairs carry dst = -1.
    """
    c, r = ids.shape
    p = si.shape[1]
    ni = jnp.take_along_axis(ids, si, axis=1)
    nj = jnp.take_along_axis(ids, sj, axis=1)
    dvi = jnp.take_along_axis(dists, si, axis=1)
    dvj = jnp.take_along_axis(dists, sj, axis=1)
    valid = (ni >= 0) & (nj >= 0) & (ni != nj)

    xi = dequant_rows(x[jnp.clip(ni, 0).reshape(-1)], scale, offset)
    xj = dequant_rows(x[jnp.clip(nj, 0).reshape(-1)], scale, offset)
    diff = xi - xj
    dij = jnp.sum(diff * diff, axis=-1).reshape(c, p)

    hit = valid & (dij < jnp.maximum(dvi, dvj))  # RNG criterion (eq. 2)
    i_is_far = dvi > dvj
    far = jnp.where(i_is_far, ni, nj)
    close = jnp.where(i_is_far, nj, ni)
    far_slot = jnp.where(i_is_far, si, sj)

    dst = jnp.where(hit, close, -1)
    kill = jnp.zeros((c, r), jnp.int32)
    rows = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[:, None], (c, p))
    kill = kill.at[rows, far_slot].max(hit.astype(jnp.int32))
    return dst, far, dij, kill.astype(bool)


def visited_probe_positions(ids: jnp.ndarray, h: int) -> jnp.ndarray:
    """Probe positions (..., HASH_PROBES) of ids in an H-slot visited table.

    Identity-mod base hash + linear probing: slot l of id v is
    (v % H + l) % H.  Vertex ids are arbitrary labels, so identity-mod is
    as uniform as any mix for permutation-invariant id assignment — and it
    is injective whenever H >= N, which makes `visited_cap >= N` provably
    collision-free (the dense-parity guarantee, DESIGN.md §6.1).
    """
    base = jnp.clip(ids.astype(jnp.int32), 0) % h
    return (base[..., None] +
            jnp.arange(HASH_PROBES, dtype=jnp.int32)) % h


def search_expand_ref(
    x: jnp.ndarray,
    queries: jnp.ndarray,
    nbrs: jnp.ndarray,
    table: jnp.ndarray,
    valid: jnp.ndarray | None = None,
    scale=None, offset=None,
    vwords: jnp.ndarray | None = None,
    fwords: jnp.ndarray | None = None,
):
    """One fused beam-search expansion step (see kernels/search_expand.py).

    Args:
      x:       (N, D) dataset (fp32/bf16/int8; `scale`/`offset` are the
               optional per-dim dequant of the stored rows — queries stay
               fp32, only the dataset side rides the precision ladder).
      queries: (Q, D) query vectors.
      nbrs:    (Q, R) int32 neighbor ids of each query's selected vertex;
               -1 marks an invalid entry (inactive query / empty slot).
               Width-agnostic: R is the raw pool width or the packed
               degree D of an optimized layout (core/layout.py); packed
               rows keep their sentinels as a tail suffix, which changes
               nothing here (the mask is positionless).
      table:   (Q, H) int32 open-addressed visited table; -1 = empty slot.
      valid:   optional (N,) bool vertex-validity mask (the dynamic index's
               tombstone mask, core/dynamic.py).  A neighbor whose vertex is
               tombstoned is treated exactly like an empty graph slot: it is
               never expanded, scored, or returned — so a later `compact()`
               (which physically drops dead vertices and their in-edges)
               cannot change any search trajectory.  None = all vertices
               live (the static-index path, bit-identical to the pre-mask
               kernel).
      vwords/fwords: the optional filtered-search predicate (core/labels.py,
               DESIGN.md §9): (N, W) packed per-vertex label-bitset words
               and (Q, W) per-query allowed-bitset words.  Semantics are
               ROUTE-THROUGH — a filtered-out neighbor keeps its real id,
               distance, and freshness (it stays fully traversable, per
               GGNN's connectivity-under-masking observation) and is only
               flagged in the extra `allowed` output, which the search
               uses to mask it out of the result heap.  Both or neither
               must be given.

    Returns (ids (Q,R) i32, dists (Q,R) f32, fresh (Q,R) bool): the
    neighbor ids (invalid/dead -> -1), exact squared query->neighbor
    distances (+inf where invalid/dead), and the freshness mask — live AND
    not found in the table's probe window.  False positives are impossible
    (exact keys); a capacity miss only re-marks an already-visited id as
    fresh, which the deduplicating beam merge absorbs.  With the filter
    operands a fourth element `allowed (Q,R) bool` is appended: live AND
    `any(vwords[id] & fwords[q])` — pure int32 bitwise math, so kernel and
    oracle agree bitwise on every precision rung.
    """
    q, r = nbrs.shape
    ok = nbrs >= 0
    if valid is not None:
        ok = ok & valid.astype(bool)[jnp.clip(nbrs, 0)]
    nv = dequant_rows(x[jnp.clip(nbrs, 0).reshape(-1)], scale,
                      offset).reshape(q, r, -1)
    diff = queries.astype(jnp.float32)[:, None, :] - nv
    d = jnp.sum(diff * diff, axis=-1)
    d = jnp.where(ok, d, jnp.inf)

    h = table.shape[1]
    pos = visited_probe_positions(nbrs, h)                    # (Q, R, PL)
    qrows = jnp.arange(q, dtype=jnp.int32)[:, None, None]
    vals = table[qrows, pos]                                  # (Q, R, PL)
    found = jnp.any(vals == nbrs[..., None], axis=-1)
    out = (jnp.where(ok, nbrs, -1), d, ok & ~found)
    if fwords is None:
        return out
    lw = vwords[jnp.clip(nbrs, 0)]                            # (Q, R, W)
    allowed = ok & jnp.any((lw & fwords[:, None, :]) != 0, axis=-1)
    return out + (allowed,)


def topr_merge_ref(
    ids: jnp.ndarray,
    dists: jnp.ndarray,
    r: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Merge candidate rows into the R closest unique entries per row.

    Args:
      ids:   (B, W) int32 candidate ids; -1 marks an empty slot.
      dists: (B, W) float32 distances to the row's owner; +inf for empty.
      r:     output pool capacity.

    Returns (out_ids (B, r) int32, out_dists (B, r) float32): per row, the r
    closest *unique* valid ids (duplicates keep their first/min-distance
    occurrence); empty slots hold (-1, +inf).

    This is the deterministic TPU-side replacement for the paper's
    WARP_INSERT (ballot dedup + replace-farthest-if-closer): keeping the R
    closest of the union dominates arrival-order replacement.
    """
    ids = ids.astype(jnp.int32)
    dists = jnp.where(ids < 0, jnp.inf, dists.astype(jnp.float32))
    if r > ids.shape[-1]:  # W < r: widen so the output is always (B, r)
        pad = r - ids.shape[-1]
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
        dists = jnp.pad(dists, ((0, 0), (0, pad)), constant_values=jnp.inf)

    # Dedup: an entry is a duplicate if an earlier slot (or an equal-position
    # slot with smaller dist) holds the same id.  O(W^2) mask — W is small.
    same = ids[..., :, None] == ids[..., None, :]                    # (B,W,W)
    earlier = jnp.tril(jnp.ones(same.shape[-2:], dtype=bool), k=-1)  # j<i
    dup = jnp.any(same & earlier[None, ...], axis=-1)                # (B,W)
    dists = jnp.where(dup, jnp.inf, dists)
    ids = jnp.where(dup, -1, ids)

    order = jnp.argsort(dists, axis=-1)[..., :r]
    out_d = jnp.take_along_axis(dists, order, axis=-1)
    out_i = jnp.take_along_axis(ids, order, axis=-1)
    out_i = jnp.where(jnp.isinf(out_d), -1, out_i)
    return out_i, out_d
