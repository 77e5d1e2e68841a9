"""Pallas TPU kernel: fused RNG propagation round (GRNND Alg. 4 inner loop).

One disordered propagation round previously lowered to a chain of separate
XLA ops: two `take_along_axis` gathers of pool slots, a materialized
(N·P, D) double gather of neighbor vectors, a `rowwise_sqdist` call, and
two scatters for the kill mask — every intermediate written to and re-read
from HBM, leaving the hot inner round memory-bound (EXPERIMENTS.md §Perf,
cell C and cell F).

This kernel fuses the whole pair-evaluation round.  Per vertex, it

  1. gathers the pool's R neighbor vectors ONCE into VMEM (kernels/rows.py:
     one DMA per row at the pool ids, issued for a block of 8 vertices per
     grid step and awaited together);
  2. evaluates, per vertex, all P sampled slot pairs in-register:
     one-hot slot selection (exact — exactly one hot per row, so the
     f32 matmul is a lossless gather), a (P, D) paired
     squared distance on the MXU/VPU, and the RNG criterion
     d(n_i, n_j) < max(d(v, n_i), d(v, n_j)) (paper eq. 2);
  3. emits the redirect requests (dst = closer endpoint, src = farther
     endpoint, the pair distance) and the per-slot kill mask in one pass.

The (N·P, D) gathered-vector intermediates never exist: HBM traffic per
vertex drops from ~2·P·D reads + 2·P·D writes + 2·P·D re-reads to R·D
reads (pool vectors, each fetched once regardless of how many sampled
pairs touch it) + the small (P,)/(R,) outputs.  See DESIGN.md §3 for
the full memory-layout discussion.

Semantics match `ref.rng_round_ref` bitwise under a common jit context
(the parity tests assert identical kill masks, redirects, and merged
pools): the slot samples si/sj are drawn OUTSIDE the kernel with the
usual jax PRNG so every backend sees the same pairs, the one-hot slot
selection is a lossless gather, and the distance math follows the same
subtract-square-reduce order as `rowwise_sqdist_ref`.

TPU notes: the one-hot gather runs at HIGHEST matmul precision, which is
exact for a 0/1 operand; R and P are small (8-64) so the per-pair arrays
ride in single vregs.  D is not padded: Mosaic masks the partial lane
tile, and its fp32 reduction tree over D may differ from XLA's, so on the
chip `dij` agrees with the oracle to ~1e-7 relative rather than bitwise
(interpret mode, the bitwise-parity harness, runs the oracle's order).
Validated under interpret=True on CPU (tests/test_rng_round.py) and
compiled for v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import rows as RW


def _rng_round_kernel(*refs, n_src: int, r: int, p: int, quantized: bool):
    """Grid: (C / BLOCK,). One step gathers the pool rows of BLOCK vertices
    (kernels/rows.py) and evaluates each vertex's P sampled pairs.

    `quantized` is the precision ladder's trace-time flag (DESIGN.md §8):
    the int8 variant carries (1, D) scale/offset operands and each gathered
    row is dequantized in VMEM — the same elementwise formula as
    `ref.dequant_rows`, so bitwise oracle parity is preserved.  The float
    rungs compile without the extra operands.
    """
    it = iter(refs)
    src = [next(it) for _ in range(n_src)]
    scale_ref, offset_ref = ((next(it), next(it)) if quantized
                             else (None, None))
    ids_ref, dists_ref, si_ref, sj_ref = (next(it), next(it), next(it),
                                          next(it))
    dst_ref, src_ref, dij_ref, kill_ref = (next(it), next(it), next(it),
                                           next(it))
    rows = RW.load_rows(src, list(it))
    bb = ids_ref.shape[0]

    slot = jax.lax.broadcasted_iota(jnp.int32, (p, r), 1)
    mm = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    for b in range(bb):                                   # static unroll
        vecs = rows[b]                                    # (R, D) f32
        if quantized:
            vecs = vecs * scale_ref[...] + offset_ref[...]
        ids_row = ids_ref[b:b + 1, :]                     # (1, R) int32
        d_row = dists_ref[b:b + 1, :]                     # (1, R) f32
        # (1, P) -> (P, 1): one lane-to-sublane relayout per vertex
        si = si_ref[b:b + 1, :].reshape(p, 1)
        sj = sj_ref[b:b + 1, :].reshape(p, 1)
        oi = si == slot                                   # (P, R) one-hot
        oj = sj == slot

        ids_b = jnp.broadcast_to(ids_row, (p, r))
        d_b = jnp.broadcast_to(d_row, (p, r))
        # exactly one hot per row -> the masked sums are exact selections
        # (where, not multiply: empty slots hold inf and 0*inf = nan)
        ni = jnp.sum(jnp.where(oi, ids_b, 0), axis=1, keepdims=True)
        nj = jnp.sum(jnp.where(oj, ids_b, 0), axis=1, keepdims=True)
        dvi = jnp.sum(jnp.where(oi, d_b, 0.0), axis=1, keepdims=True)
        dvj = jnp.sum(jnp.where(oj, d_b, 0.0), axis=1, keepdims=True)

        xi = mm(oi.astype(jnp.float32), vecs)             # (P, D) exact gather
        xj = mm(oj.astype(jnp.float32), vecs)
        diff = xi - xj
        dij = jnp.sum(diff * diff, axis=1, keepdims=True)  # (P, 1)

        valid = (ni >= 0) & (nj >= 0) & (ni != nj)
        hit = valid & (dij < jnp.maximum(dvi, dvj))        # RNG criterion
        i_is_far = dvi > dvj
        far = jnp.where(i_is_far, ni, nj)
        close = jnp.where(i_is_far, nj, ni)
        far_slot = jnp.where(i_is_far, si, sj)             # (P, 1)

        dst_ref[b:b + 1, :] = jnp.where(hit, close, -1).reshape(1, p)
        src_ref[b:b + 1, :] = far.reshape(1, p)
        dij_ref[b:b + 1, :] = dij.reshape(1, p)
        # kill[rr] = any sampled hit whose farther endpoint sits in slot rr
        o_far = (far_slot == slot) & hit                   # (P, R)
        kill_ref[b:b + 1, :] = jnp.max(o_far.astype(jnp.int32), axis=0,
                                       keepdims=True)      # (1, R)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rng_round_pallas(
    x: jnp.ndarray,
    ids: jnp.ndarray,
    dists: jnp.ndarray,
    si: jnp.ndarray,
    sj: jnp.ndarray,
    scale: jnp.ndarray | None = None,
    offset: jnp.ndarray | None = None,
    *,
    interpret: bool = False,
):
    """Fused propagation round over a (C, R) pool chunk.

    Args:
      x:     (N, D) dataset (stays in HBM; rows are gathered on demand;
             fp32/bf16/int8 storage per the precision ladder).
      ids:   (C, R) int32 pool ids, -1 = empty slot.
      dists: (C, R) f32 owner distances, +inf = empty.
      si/sj: (C, P) int32 sampled slot indices in [0, R).
      scale/offset: optional (D,) per-dim dequant of the stored x rows,
             fused into the row load (None = float storage).

    Returns (dst (C,P) i32, src (C,P) i32, dij (C,P) f32, kill (C,R) bool):
    the redirect requests (dst = -1 where the pair missed) and the slot
    kill mask — identical to `ref.rng_round_ref`.
    """
    c, r = ids.shape
    n, d = x.shape
    p = si.shape[1]
    quantized = scale is not None
    bb = RW.BLOCK
    # pad the chunk to whole blocks with empty vertices (no valid pair, so
    # no request and no kill) and slice them off the outputs
    ids_p = RW.pad_rows(ids.astype(jnp.int32), bb, -1)
    dists_p = RW.pad_rows(dists.astype(jnp.float32), bb, jnp.inf)
    si_p = RW.pad_rows(si.astype(jnp.int32), bb, 0)
    sj_p = RW.pad_rows(sj.astype(jnp.int32), bb, 0)
    cp = ids_p.shape[0]
    src_ops, src_specs, scratch = RW.row_source(
        x, jnp.clip(ids_p, 0, n - 1))

    q_ops, q_specs = (), []
    if quantized:
        q_ops = tuple(v.astype(jnp.float32).reshape(1, d)
                      for v in (scale, offset))
        q_specs = [pl.BlockSpec((1, d), lambda i: (0, 0))] * 2

    def blk(w):
        return pl.BlockSpec((bb, w), lambda i: (i, 0))

    dst, src, dij, kill = pl.pallas_call(
        functools.partial(_rng_round_kernel, n_src=len(src_ops), r=r, p=p,
                          quantized=quantized),
        grid=(cp // bb,),
        in_specs=src_specs + q_specs + [blk(r), blk(r), blk(p), blk(p)],
        out_specs=[blk(p), blk(p), blk(p), blk(r)],
        out_shape=[
            jax.ShapeDtypeStruct((cp, p), jnp.int32),
            jax.ShapeDtypeStruct((cp, p), jnp.int32),
            jax.ShapeDtypeStruct((cp, p), jnp.float32),
            jax.ShapeDtypeStruct((cp, r), jnp.int32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*src_ops, *q_ops, ids_p, dists_p, si_p, sj_p)
    return dst[:c], src[:c], dij[:c], kill[:c].astype(bool)
