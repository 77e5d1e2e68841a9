"""Dataset-row gathers shared by the fused kernels: HBM rows -> VMEM.

`rng_round`, `search_expand` and `gather_l2` each read rows of an (N, D)
dataset at data-dependent ids.  A BlockSpec cannot express that gather on
a TPU: Mosaic needs the last two dims of a block to be multiples of
(8, 128) or whole, so a (1, D) row block over (N, D) is refused.  Instead
the kernels walk their (B, K) id table in blocks of `bb` rows per grid
step, and this module supplies the K dataset rows of every table row:

  * 32-bit rows (fp32 storage): the dataset stays in HBM (`pl.ANY`), the
    block's ids ride an SMEM block, and the kernel starts one DMA per row
    into a (bb, K, D) VMEM scratch, then waits for all of them.  Only the
    rows a step needs cross HBM, once each.
  * packed rows (bf16, int8): Mosaic tiles these by 8 rows in HBM and
    refuses a 1-row DMA, so the wrapper gathers the rows with XLA first
    (one (B, K, D) array at storage width), the kernel reads them as an
    ordinary (bb, K, D) block and widens it into an fp32 VMEM scratch.
    That writes and re-reads the gathered bytes once more than the DMA
    path does.

Either way the kernel sees a (bb, K, D) fp32 ref holding the stored values
exactly (dequant, where the rung has one, follows in the kernel), so the
kernel/oracle parity contract is unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# table rows per grid step: one sublane tile, the smallest legal block
BLOCK = 8


def pad_rows(a: jnp.ndarray, mult: int, value) -> jnp.ndarray:
    """Pad axis 0 of `a` up to a multiple of `mult` with `value`."""
    pad = (-a.shape[0]) % mult
    if pad == 0:
        return a
    widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=value)


def row_source(x: jnp.ndarray, ids: jnp.ndarray):
    """(operands, in_specs, scratch_shapes) that deliver x[ids] per block.

    `ids` is a (B, K) int32 table already clamped to [0, N) with B a
    multiple of BLOCK; the grid is (B // BLOCK,).  Pass the operands first to
    the pallas_call, and hand the matching refs to `load_rows`.
    """
    bb, k = BLOCK, ids.shape[1]
    d = x.shape[1]
    if jnp.dtype(x.dtype).itemsize == 4:
        return ((ids, x),
                [pl.BlockSpec((bb, k), lambda i: (i, 0),
                              memory_space=pltpu.SMEM),
                 pl.BlockSpec(memory_space=pl.ANY)],
                [pltpu.VMEM((bb, k, d), x.dtype),
                 pltpu.SemaphoreType.DMA(())])
    return ((x[ids],), [pl.BlockSpec((bb, k, d), lambda i: (i, 0, 0))],
            [pltpu.VMEM((bb, k, d), jnp.float32)])


def load_rows(src, scratch):
    """In-kernel: -> a (bb, K, D) fp32 ref holding this block's rows.

    `src`/`scratch` are the refs `row_source` set up (2 + 2 on the DMA
    path, 1 + 1 on the pre-gathered path).  All DMAs of the block are in
    flight together and share one semaphore; each wait consumes one row.
    """
    if len(src) == 1:
        # widen once into VMEM: the kernels then start from the same
        # materialized fp32 rows as the oracle's `dequant_rows`
        scratch[0][...] = src[0][...].astype(jnp.float32)
        return scratch[0]
    ids_ref, x_hbm = src
    buf, sem = scratch
    bb, k, _ = buf.shape

    def copy(t):
        b, j = t // k, t % k
        return pltpu.make_async_copy(x_hbm.at[pl.ds(ids_ref[b, j], 1)],
                                     buf.at[b, pl.ds(j, 1)], sem)

    def start(t, c):
        copy(t).start()
        return c

    def wait(t, c):
        copy(t).wait()
        return c

    jax.lax.fori_loop(0, bb * k, start, 0)
    jax.lax.fori_loop(0, bb * k, wait, 0)
    return buf
