"""Pallas TPU kernel: gather-fused paired distances.

The §Perf analysis of the GRNND build (EXPERIMENTS.md cell C) shows the
dominant bytes are the materialized gathers x[ni], x[nj] — (M, D) matrices
written to and re-read from HBM just to be subtracted.  On TPU the gather
is instead fused into the distance computation: each grid step gathers the
two rows of 512 pairs HBM->VMEM (kernels/rows.py: one DMA per row at the
ids of an SMEM block), squares-and-reduces on the VPU, and writes one
(8, 64) block.  The (M, D) intermediates never exist.

HBM traffic: 2·M·D·4 bytes of reads + M·4 writes — versus the unfused
2·(M·D reads + M·D writes + M·D re-reads) ≈ 3x reduction, plus the removal
of two big HBM buffers.

Validated under interpret=True against ref.gather_sqdist_ref
(tests/test_precision.py) and compiled for v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import rows as RW

# 64 pairs per id-table row: the row holds their 64 first ids, then their
# 64 second ids, so the (M / 64, 128) table is lane-dense in HBM (an (M, 2)
# table would be padded to 128 lanes: 12 GB at M = 24M)
PAIRS_PER_ROW = 64


def _gather_l2_kernel(*refs, n_src: int, quantized: bool):
    """Grid: (M / (BLOCK · 64),). The (BLOCK, 128, D) block holds both rows
    of BLOCK · 64 pairs, gathered per kernels/rows.py.

    `quantized` (the precision ladder, DESIGN.md §8) is a trace-time flag:
    the int8 variant carries (1, D) scale/offset operands, and both rows
    are dequantized with the same elementwise formula as
    `ref.dequant_rows` before the subtract-square-reduce — bitwise oracle
    parity preserved.
    """
    it = iter(refs)
    src = [next(it) for _ in range(n_src)]
    scale_ref, offset_ref = ((next(it), next(it)) if quantized
                             else (None, None))
    o_ref = next(it)
    rows = RW.load_rows(src, list(it))                   # (bb, 128, D) f32
    h = PAIRS_PER_ROW
    for b in range(o_ref.shape[0]):                      # static unroll
        xi, xj = rows[b, :h, :], rows[b, h:, :]
        if quantized:
            xi = xi * scale_ref[...] + offset_ref[...]
            xj = xj * scale_ref[...] + offset_ref[...]
        diff = xi - xj
        o_ref[b:b + 1, :] = jnp.sum(diff * diff, axis=-1).reshape(1, h)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_sqdist_pallas(
    x: jnp.ndarray,
    ni: jnp.ndarray,
    nj: jnp.ndarray,
    scale: jnp.ndarray | None = None,
    offset: jnp.ndarray | None = None,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """d(x[ni[m]], x[nj[m]]) for m in [0, M) without materialized gathers.

    x (N, D) stays in HBM; per grid step the rows of BLOCK · 64 pairs are
    gathered into VMEM.  Invalid indices (< 0) are clamped; callers mask
    them.  scale/offset are the precision ladder's optional (D,) per-dim
    dequant of the stored x rows (None = float storage).
    """
    m = ni.shape[0]
    n, d = x.shape
    quantized = scale is not None
    bb, h = RW.BLOCK, PAIRS_PER_ROW

    def table(v):  # (M,) -> (M_pad / 64, 64), zero-padded, clamped
        v = RW.pad_rows(jnp.clip(v.astype(jnp.int32), 0, n - 1), bb * h, 0)
        return v.reshape(-1, h)

    ids = jnp.concatenate([table(ni), table(nj)], axis=1)   # (T, 128)
    src_ops, src_specs, scratch = RW.row_source(x, ids)

    q_ops, q_specs = (), []
    if quantized:
        q_ops = tuple(v.astype(jnp.float32).reshape(1, d)
                      for v in (scale, offset))
        q_specs = [pl.BlockSpec((1, d), lambda i: (0, 0))] * 2

    out = pl.pallas_call(
        functools.partial(_gather_l2_kernel, n_src=len(src_ops),
                          quantized=quantized),
        grid=(ids.shape[0] // bb,),
        in_specs=src_specs + q_specs,
        out_specs=pl.BlockSpec((bb, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ids.shape[0], h), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*src_ops, *q_ops)
    return out.reshape(-1)[:m]
