"""Pallas TPU kernel: fused beam-search expansion step.

One expansion step of the batched beam search (core/search.py) previously
lowered to the same unfused shape as the old propagation round: a
materialized (Q·R, D) gather of the selected vertex's neighbor vectors, a
`jnp.repeat` of the queries to match, a `rowwise_sqdist` over the pair, and
a separate dense visited-bitmask lookup — every intermediate written to and
re-read from HBM, on the query-serving hot path (EXPERIMENTS.md §Perf
cell E; GGNN's fused gather-and-distance expansion is the GPU analogue).

This kernel fuses the whole step.  Per query q it

  1. gathers the R neighbor vectors of the selected vertex ONCE into VMEM
     (kernels/rows.py: one DMA per row at the clamped neighbor ids, for a
     block of 8 queries per grid step, awaited together);
  2. computes all R query->neighbor squared distances in-register
     (subtract-square-reduce, the `rowwise_sqdist_ref` order);
  3. probes the query's visited set, one of two:
     - the open-addressed table (H int32 slots, identity-mod hash +
       linear probe window, DESIGN.md §6.1): the table is wrap-extended
       by PROBES slots outside the kernel, so each id's probe window is
       one contiguous run of PROBES slots; the kernel compares each id
       against the whole (H + PROBES)-slot row under a window mask
       (Mosaic has no dynamic lane slice);
     - the dense bitmap (`ref.visited_bitmap`, N/8 bytes per query): the
       block's bitmap streams through VMEM aliased in place, each id
       reads one 128-word row of it, and the fresh ids' bits are set in
       the same pass, while the row DMAs of step 1 are in flight;
     and emits (ids, dists, fresh-mask) in one pass;
  4. applies the optional (N,) vertex-validity mask (the dynamic index's
     tombstone mask, core/dynamic.py §DESIGN.md §7): the wrapper gathers
     each neighbor's validity bit with XLA (4 bytes next to a D-wide row)
     and a dead neighbor is reported exactly like an empty graph slot
     (id -1, dist +inf, not fresh);
  5. evaluates the optional per-query label predicate (filtered search,
     core/labels.py, DESIGN.md §9): the neighbors' (W,) packed label-bitset
     words, gathered the same way, intersect with the query's
     allowed-bitset block, and emit an extra `allowed` output — ROUTE-
     THROUGH semantics, so ids/dists/fresh are untouched (the filtered-out
     neighbor stays traversable; only the result heap masks it).

The (Q·R, D) gathered-vector and repeated-query intermediates never exist:
HBM traffic per step drops from ~3·(Q·R·D + Q·D·R) read/write/re-read bytes
to R·D reads per query plus the small (Q, R) outputs.

Membership semantics: `fresh[q, j]` is true iff nbrs[q, j] is a valid id
AND the id is NOT stored in the table's probe window — false positives are
impossible (exact int32 keys, not fingerprints), so a hash-capacity miss
can only cause a harmless re-expansion, never a wrongly-skipped vertex.
Table *updates* stay outside the kernel (core/search.py inserts after the
step), so on that path the kernel is a pure read; corpus sharding passes
a (Q, 1) all-empty table, a no-op probe, and keeps its own visited set.
The bitmap is exact: fresh iff valid and the bit is clear, every id
probed before any bit is set, the updated bitmap returned.

Graph-row layout contract (core/layout.py): callers hand this kernel the
ALREADY-GATHERED (Q, R) neighbor-id rows of the selected vertices, so the
optimized index's packed fixed-degree adjacency needs no kernel variant —
R simply becomes the packed degree D.  The packed rows additionally
guarantee -1 sentinels appear only as a tail suffix (rank-ordered valid ids
first), which the kernel tolerates anywhere but the DMA schedule rewards:
a packed row's clamped sentinel gathers are contiguous repeats of row 0
instead of interleaved holes, and the locality renumbering makes the
nb_ref[q, rr] row indices near-sequential across the beam.

Semantics match `ref.search_expand_ref` bitwise under a common jit context
(tests/test_search_parity.py): probe positions follow the same
identity-mod + linear-probe formula and the distance reduction follows the
same subtract-square-reduce order.  D is not padded: on the chip Mosaic's
fp32 reduction tree over D may differ from XLA's, so distances agree with
the oracle to ~1e-7 relative there rather than bitwise; interpret mode —
the bitwise parity harness — runs the oracle's order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rows as RW
# Single source of truth for the probe-window length (shared with the
# oracle and the table-insert path in core/search.py).
from repro.kernels.ref import HASH_PROBES


def _visit_bitmap(bm_in, bm_out, okid_ref, nbrs_ref, words, found_ref):
    """Probe, then set, the dense visited bits of the block's queries.

    Vertex v is bit v & 31 of lane (v >> 5) & 127 of sublane (v >> 12) & 7
    in tile v >> 15 of its query's block (`ref.bitmap_slots`).  Per query,
    each id's bitmap row is copied into row j of the (R, 128) `words`
    scratch, one lane select then gives all R words at once, and found
    (R bits) goes to `found_ref`.  Every id is probed in `bm_in` before
    any bit is set in `bm_out` (on the chip the two are one VMEM buffer,
    the operand being aliased), so a duplicated id is fresh in each
    slot, as in the oracle.  `okid_ref` (SMEM) holds the ids, -1 where not ok;
    setting the bit of every ok id sets exactly the fresh ones, since the
    others are set already.
    """
    bq, r = found_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    bm_out[...] = bm_in[...]

    def query(b, c):
        # a loop, not a static unroll: the unrolled id loops below are the
        # big part of the kernel's trace, which every process lowers anew

        def row(j):
            u = jnp.maximum(okid_ref[b, j], 0)
            return u >> 15, pl.ds((u >> 12) & 7, 1), u

        def probe(j, c):
            t, s, _ = row(j)
            words[pl.ds(j, 1), :] = bm_in[b, t, s, :]
            return c

        def mark(j, c):
            t, s, u = row(j)
            one = jnp.where(okid_ref[b, j] >= 0,
                            jnp.left_shift(jnp.int32(1), u & 31), 0)
            bm_out[b, t, s, :] = bm_out[b, t, s, :] | jnp.where(
                lane == ((u >> 5) & 127), one, 0)
            return c

        jax.lax.fori_loop(0, r, probe, 0, unroll=True)
        v = jnp.maximum(nbrs_ref[pl.ds(b, 1), :], 0).reshape(r, 1)
        w = jnp.sum(jnp.where(lane == ((v >> 5) & 127), words[...], 0),
                    axis=1, keepdims=True)            # (R, 1) words
        found_ref[pl.ds(b, 1), :] = ((w >> (v & 31)) & 1).reshape(1, r)
        jax.lax.fori_loop(0, r, mark, 0, unroll=True)
        return c

    jax.lax.fori_loop(0, bq, query, 0)


def _search_expand_kernel(*refs, n_src: int, r: int, h: int, probes: int,
                          masked: bool, quantized: bool, filtered: bool,
                          dense: bool):
    """Grid: (Q / BLOCK,). One step gathers the neighbor rows of BLOCK
    queries (kernels/rows.py) and evaluates distances + probes per query.

    `masked` is a trace-time flag: the static-index path (valid=None)
    compiles WITHOUT the validity operand — the dynamic feature costs the
    hot serving loop nothing unless it is used.  `quantized` (the
    precision ladder, DESIGN.md §8) likewise: the int8 variant carries
    (1, D) scale/offset operands and dequantizes each gathered neighbor
    row in VMEM — the same elementwise formula as `ref.dequant_rows`
    (bitwise oracle parity); queries stay fp32.  `filtered` (filtered
    search, DESIGN.md §9) is the same idiom again: the neighbors' (R, W)
    packed label-bitset words and the query's (1, W) allowed-bitset words
    are blocks, and the intersection test emits the extra `allowed`
    output — route-through semantics, so ids/dists/fresh are UNCHANGED by
    the predicate (the neighbor stays traversable either way).  `dense`
    swaps the hashed table for the dense visited bitmap: the block's ok
    ids ride an SMEM block, the (BLOCK, tiles, 8, 128) bitmap block comes
    in and goes out aliased in place, and `_visit_bitmap` probes and sets
    it.
    """
    it = iter(refs)
    src = [next(it) for _ in range(n_src)]
    live_ref = next(it) if masked else None
    lab_ref = next(it) if filtered else None
    scale_ref, offset_ref = ((next(it), next(it)) if quantized
                             else (None, None))
    q_ref, nbrs_ref = next(it), next(it)
    if dense:
        okid_ref, bm_in = next(it), next(it)
    else:
        tab_ref = next(it)
    fw_ref = next(it) if filtered else None
    ids_ref, d_ref, fresh_ref = next(it), next(it), next(it)
    alw_ref = next(it) if filtered else None
    if dense:
        bm_out, words = next(it), next(it)
        # the visited work runs while the row DMAs are in flight; found
        # bits wait in fresh_ref until the per-query pass below
        rows = RW.load_rows(src, list(it), during=lambda: _visit_bitmap(
            bm_in, bm_out, okid_ref, nbrs_ref, words, fresh_ref))
    else:
        rows = RW.load_rows(src, list(it))
        pos = jax.lax.broadcasted_iota(jnp.int32, (r, tab_ref.shape[1]), 1)
    bq = q_ref.shape[0]

    for b in range(bq):                               # static unroll
        vecs = rows[b]                                # (R, D) f32
        if quantized:
            vecs = vecs * scale_ref[...] + offset_ref[...]
        qv = q_ref[b:b + 1, :].astype(jnp.float32)    # (1, D)
        nb = nbrs_ref[b:b + 1, :]                     # (1, R) int32
        diff = vecs - qv                              # (R, D) broadcast
        d = jnp.sum(diff * diff, axis=1).reshape(1, r)

        if dense:
            found = fresh_ref[b:b + 1, :]
        else:
            # wrap-extended table (1, H + PROBES): slot (v % H + l) % H of
            # the H-slot table is slot (v % H) + l here, so each id's probe
            # window is the contiguous run [base, base + PROBES) of this row
            v = nb.reshape(r, 1)
            base = jnp.clip(v, 0) % h
            win = (pos >= base) & (pos < base + probes)
            hit = win & (tab_ref[b:b + 1, :] == v)    # (R, H + PROBES)
            found = jnp.max(hit.astype(jnp.int32), axis=1).reshape(1, r)

        # a tombstoned neighbor (valid[v] == 0) is indistinguishable from an
        # empty graph slot: never scored, never returned (ref.py contract)
        ok = nb >= 0
        if masked:
            ok = ok & (live_ref[b:b + 1, :] != 0)
        ids_ref[b:b + 1, :] = jnp.where(ok, nb, -1)
        d_ref[b:b + 1, :] = jnp.where(ok, d, jnp.inf)
        fresh_ref[b:b + 1, :] = (ok & (found == 0)).astype(jnp.int32)
        if filtered:
            # pure int32 bitwise intersection: bitwise-equal to the
            # oracle's `any(vwords[id] & fwords[q])` on every rung
            inter = (lab_ref[b] & fw_ref[b:b + 1, :]) != 0   # (R, W)
            allow = jnp.max(inter.astype(jnp.int32), axis=1).reshape(1, r)
            alw_ref[b:b + 1, :] = (ok & (allow != 0)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def search_expand_pallas(
    x: jnp.ndarray,
    queries: jnp.ndarray,
    nbrs: jnp.ndarray,
    table: jnp.ndarray,
    valid: jnp.ndarray | None = None,
    scale: jnp.ndarray | None = None,
    offset: jnp.ndarray | None = None,
    vwords: jnp.ndarray | None = None,
    fwords: jnp.ndarray | None = None,
    bitmap: jnp.ndarray | None = None,
    *,
    interpret: bool = False,
):
    """Fused expansion step over a (Q, R) neighbor-id batch.

    Args:
      x:       (N, D) dataset (stays in HBM; rows are gathered on demand;
               fp32/bf16/int8 storage per the precision ladder).
      queries: (Q, D) query vectors (always fp32 — only the stored dataset
               side rides the ladder).
      nbrs:    (Q, R) int32 neighbor ids of each query's selected vertex,
               -1 = invalid (inactive query or empty graph slot).  R is
               the graph row width: the pool width of a raw GRNND index,
               or the packed degree D of an optimized layout
               (core/layout.py) — the kernel is width-agnostic.
      table:   (Q, H) int32 open-addressed visited table, -1 = empty slot;
               None when `bitmap` is given.
      valid:   optional (N,) bool/int32 vertex-validity mask (tombstones,
               core/dynamic.py); each neighbor's bit is gathered beside
               its row.  None = all live.
      scale/offset: optional (D,) per-dim dequant of the stored x rows,
               fused into the row load (None = float storage).
      vwords/fwords: optional filtered-search predicate (core/labels.py):
               (N, W) packed per-vertex label words + (Q, W) per-query
               allowed words; both or neither.
      bitmap:  the dense visited set in place of `table`: (Q, rows, 128)
               int32 (`ref.visited_bitmap`), probed and updated in place.

    Returns (ids (Q,R) i32, dists (Q,R) f32, fresh (Q,R) bool) — identical
    to `ref.search_expand_ref`; with the filter operands, a fourth element
    `allowed (Q,R) bool` (route-through: ids/dists/fresh are unchanged);
    with `bitmap`, the updated bitmap last.
    """
    qn, r = nbrs.shape
    n, d = x.shape
    masked = valid is not None  # trace-time: None is a distinct jit trace
    quantized = scale is not None
    filtered = fwords is not None
    dense = bitmap is not None
    assert filtered == (vwords is not None), \
        "vwords and fwords must be given together"
    assert dense == (table is None), "give exactly one of table and bitmap"
    bq = RW.BLOCK
    # pad the batch to whole blocks with inactive queries (all ids -1)
    nbrs_p = RW.pad_rows(nbrs.astype(jnp.int32), bq, -1)
    qp = RW.pad_rows(queries, bq, 0.0)
    nbrs_safe = jnp.clip(nbrs_p, 0, n - 1)
    src_ops, src_specs, scratch = RW.row_source(x, nbrs_safe)

    def blk(w):
        return pl.BlockSpec((bq, w), lambda i: (i, 0))

    # per-neighbor validity bits and label words: XLA gathers of a few
    # bytes per neighbor, handed to the kernel as ordinary blocks
    mask_ops, mask_specs = (), []
    if masked:
        mask_ops = (valid.astype(jnp.int32)[nbrs_safe],)
        mask_specs = [blk(r)]

    if dense:
        # the ok ids (-1 where empty or dead) as SMEM scalars, and the
        # bitmap as (BLOCK, tiles, 8, 128) blocks: the same bytes, viewed
        # so that each id's tile is a leading-axis index
        okid = nbrs_p if not masked else jnp.where(mask_ops[0] != 0,
                                                   nbrs_p, -1)
        rows = bitmap.shape[1]
        bm = RW.pad_rows(bitmap, bq, 0).reshape(-1, rows // 8, 8, 128)
        bm_spec = pl.BlockSpec((bq, rows // 8, 8, 128),
                               lambda i: (i, 0, 0, 0))
        vis_ops = (okid, bm)
        vis_specs = [pl.BlockSpec((bq, r), lambda i: (i, 0),
                                  memory_space=pltpu.SMEM), bm_spec]
        h = 0
    else:
        # wrap-extend the table so every (mod H) probe window is
        # contiguous: ext[base + l] == table[(base + l) % H] for base < H,
        # l < PROBES (tiled, not a single concat, so H < PROBES also
        # wraps correctly)
        h = table.shape[1]
        reps = 1 + -(-HASH_PROBES // h)
        tab_ext = jnp.tile(RW.pad_rows(table.astype(jnp.int32), bq, -1),
                           (1, reps))[:, :h + HASH_PROBES]
        vis_ops = (tab_ext,)
        vis_specs = [blk(h + HASH_PROBES)]
    lab_ops, lab_specs, fw_ops, fw_specs = (), [], (), []
    alw_shape, alw_specs = [], []
    if filtered:
        w = vwords.shape[1]
        lab_ops = (vwords.astype(jnp.int32)[nbrs_safe],)
        lab_specs = [pl.BlockSpec((bq, r, w), lambda i: (i, 0, 0))]
        fw_ops = (RW.pad_rows(fwords.astype(jnp.int32), bq, 0),)
        fw_specs = [blk(w)]
        alw_shape = [jax.ShapeDtypeStruct(nbrs_p.shape, jnp.int32)]
        alw_specs = [blk(r)]

    q_ops, q_specs = (), []
    if quantized:
        q_ops = tuple(v.astype(jnp.float32).reshape(1, d)
                      for v in (scale, offset))
        q_specs = [pl.BlockSpec((1, d), lambda i: (0, 0))] * 2

    operands = (*src_ops, *mask_ops, *lab_ops, *q_ops, qp, nbrs_p, *vis_ops,
                *fw_ops)
    bm_shape, bm_specs, aliases = [], [], {}
    if dense:
        bm_shape = [jax.ShapeDtypeStruct(bm.shape, jnp.int32)]
        bm_specs = [bm_spec]
        aliases = {len(operands) - len(fw_ops) - 1: 3 + len(alw_shape)}
    out = pl.pallas_call(
        functools.partial(_search_expand_kernel, n_src=len(src_ops), r=r,
                          h=h, probes=HASH_PROBES, masked=masked,
                          quantized=quantized, filtered=filtered,
                          dense=dense),
        grid=(nbrs_p.shape[0] // bq,),
        in_specs=(src_specs + mask_specs + lab_specs + q_specs
                  + [blk(d), blk(r)] + vis_specs + fw_specs),
        out_specs=[blk(r), blk(r), blk(r)] + alw_specs + bm_specs,
        out_shape=[
            jax.ShapeDtypeStruct(nbrs_p.shape, jnp.int32),
            jax.ShapeDtypeStruct(nbrs_p.shape, jnp.float32),
            jax.ShapeDtypeStruct(nbrs_p.shape, jnp.int32),
        ] + alw_shape + bm_shape,
        scratch_shapes=([pltpu.VMEM((r, 128), jnp.int32)] if dense else [])
        + scratch,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*operands)
    out = [o[:qn] for o in out]
    if dense:
        out[-1] = out[-1].reshape(qn, rows, 128)
    out[2] = out[2].astype(bool)
    if filtered:
        out[3] = out[3].astype(bool)
    return tuple(out)
