"""Pallas TPU kernel: blocked pairwise squared-L2 distance.

This is the paper's hot spot (GRNND §3.4, WARP_DISTANCE).  On the GPU a warp
strides the vector dimensions and tree-reduces with __shfl_down; the TPU-
native formulation feeds the MXU instead: for a (BM, BK) tile of X and a
(BN, BK) tile of Y the partial squared distance is

    ||x||^2_slab + ||y||^2_slab - 2 * x @ y.T

accumulated over D-slabs in fp32.  BlockSpecs keep one X slab, one Y slab and
the (BM, BN) accumulator resident in VMEM; slab size is chosen so the working
set stays well under the ~16 MiB/core budget while the contraction dimension
remains a multiple of the 128-lane MXU width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _pairwise_kernel(x_ref, y_ref, *refs, xq: bool, yq: bool):
    """Grid: (M/BM, N/BN, D/BK).  Accumulates over the k axis.

    `xq`/`yq` are trace-time flags for the precision ladder (DESIGN.md §8):
    a quantized side carries a (1, BK) scale and offset slab, and its rows
    are dequantized in VMEM right after the fp32 widen — the same
    elementwise `dequant_rows` formula as the ref.py oracle, so the fused
    dequant changes nothing about oracle parity.  The fp32/bf16 path
    compiles without the extra operands.
    """
    it = iter(refs)
    sx_ref, ox_ref = (next(it), next(it)) if xq else (None, None)
    sy_ref, oy_ref = (next(it), next(it)) if yq else (None, None)
    o_ref = next(it)
    k = pl.program_id(2)
    x = x_ref[...].astype(jnp.float32)  # (BM, BK)
    y = y_ref[...].astype(jnp.float32)  # (BN, BK)
    if xq:
        x = x * sx_ref[...] + ox_ref[...]
    if yq:
        y = y * sy_ref[...] + oy_ref[...]
    xx = jnp.sum(x * x, axis=-1, keepdims=True)                    # (BM, 1)
    yy = jnp.sum(y * y, axis=-1)[None, :]                          # (1, BN)
    xy = jax.lax.dot_general(
        x, y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,   # full f32 on the MXU
        preferred_element_type=jnp.float32,
    )                                                              # (BM, BN)
    partial = xx + yy - 2.0 * xy

    @pl.when(k == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(k != 0)
    def _acc():
        o_ref[...] += partial


def _pad_to(a: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def pairwise_sqdist_pallas(
    x: jnp.ndarray,
    y: jnp.ndarray,
    x_scale: jnp.ndarray | None = None,
    x_offset: jnp.ndarray | None = None,
    y_scale: jnp.ndarray | None = None,
    y_offset: jnp.ndarray | None = None,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jnp.ndarray:
    """Squared L2 distances between rows of x (M,D) and y (N,D) -> (M,N) fp32.

    Either side may be stored quantized (int8 + per-dim (D,) scale/offset,
    the precision ladder): the dequant is fused into the tile load.  The
    scale/offset slabs are ZERO-padded along D, so padded columns dequant
    to exactly 0 and contribute nothing to any distance.
    """
    m, d = x.shape
    n, d2 = y.shape
    assert d == d2, f"dim mismatch {d} vs {d2}"
    bk = min(bk, max(128, d))
    xq = x_scale is not None
    yq = y_scale is not None

    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    yp = _pad_to(_pad_to(y, 0, bn), 1, bk)
    mp, dp = xp.shape
    np_, _ = yp.shape

    def _qslab(v):  # (D,) -> (1, dp), zero-padded
        return _pad_to(v.astype(jnp.float32).reshape(1, d), 1, bk)

    qspec = pl.BlockSpec((1, bk), lambda i, j, k: (0, k))
    ops_q, specs_q = [], []
    if xq:
        ops_q += [_qslab(x_scale), _qslab(x_offset)]
        specs_q += [qspec, qspec]
    if yq:
        ops_q += [_qslab(y_scale), _qslab(y_offset)]
        specs_q += [qspec, qspec]

    grid = (mp // bm, np_ // bn, dp // bk)
    out = pl.pallas_call(
        functools.partial(_pairwise_kernel, xq=xq, yq=yq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ] + specs_q,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(xp, yp, *ops_q)
    return jnp.maximum(out[:m, :n], 0.0)


def _rowwise_kernel(x_ref, y_ref, o_ref):
    """Grid: (M/BM, D/BK). Row-paired squared distance, accumulated over k."""
    k = pl.program_id(1)
    diff = x_ref[...].astype(jnp.float32) - y_ref[...].astype(jnp.float32)
    partial = jnp.sum(diff * diff, axis=-1, keepdims=True)  # (BM, 1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(k != 0)
    def _acc():
        o_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def rowwise_sqdist_pallas(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    bm: int = 256,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jnp.ndarray:
    """Row-paired squared L2: x (M,D), y (M,D) -> (M,) fp32."""
    m, d = x.shape
    assert y.shape == x.shape
    bk = min(bk, max(128, d))

    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    yp = _pad_to(_pad_to(y, 0, bm), 1, bk)
    mp, dp = xp.shape

    grid = (mp // bm, dp // bk)
    out = pl.pallas_call(
        _rowwise_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        interpret=interpret,
    )(xp, yp)
    return out[:m, 0]
