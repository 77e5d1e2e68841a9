"""Pallas TPU kernel: per-row dedup + top-R-by-distance merge.

This is the TPU-native replacement for the paper's WARP_INSERT (GRNND §3.4,
Alg. 6): the GPU version uses __ballot for set-membership and an atomic
replace-farthest; here a whole row (pool ∪ incoming candidates, width W) is
resident in VMEM/VREGs and processed with pure vector ops:

  * dedup       — O(W^2) equality mask on the VPU, the "ballot" analogue;
  * selection   — R rounds of (min, first-match one-hot, mask-out), the
                  deterministic analogue of replace-farthest-if-closer.

No gathers, no scatter, no atomics: each grid step owns BR independent rows.
The one-hot selection avoids per-row dynamic indexing, which keeps the kernel
fully vectorized on 8x128 vregs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BR = 8


def _topr_merge_kernel(ids_ref, dists_ref, oi_ref, od_ref, *, r: int):
    ids = ids_ref[...]                       # (BR, W) int32
    dists = dists_ref[...].astype(jnp.float32)
    dists = jnp.where(ids < 0, jnp.inf, dists)

    # --- dedup ("ballot"): later slot with an id seen earlier is invalid ---
    same = ids[:, :, None] == ids[:, None, :]            # (BR, W, W)
    w = ids.shape[1]
    earlier = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1) < \
        jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)   # earlier[i, j] = j < i
    dup = jnp.any(same & earlier[None], axis=-1)
    dists = jnp.where(dup, jnp.inf, dists)

    # --- R selection rounds: extract first-min, mask it out ---
    lane = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 1)
    out_ids = []
    out_dists = []
    for _ in range(r):
        minv = jnp.min(dists, axis=-1, keepdims=True)            # (BR, 1)
        # first minimum: the lowest lane holding the min (no cumsum —
        # Mosaic has no lowering for it)
        at = jnp.min(jnp.where(dists == minv, lane, w), axis=-1,
                     keepdims=True)
        first = lane == at
        sel_id = jnp.sum(jnp.where(first, ids, 0), axis=-1)      # (BR,)
        valid = jnp.isfinite(minv[:, 0])
        out_ids.append(jnp.where(valid, sel_id, -1))
        out_dists.append(jnp.where(valid, minv[:, 0], jnp.inf))
        dists = jnp.where(first, jnp.inf, dists)

    oi_ref[...] = jnp.stack(out_ids, axis=-1)
    od_ref[...] = jnp.stack(out_dists, axis=-1)


@functools.partial(jax.jit, static_argnames=("r", "br", "interpret"))
def topr_merge_pallas(
    ids: jnp.ndarray,
    dists: jnp.ndarray,
    r: int,
    *,
    br: int = DEFAULT_BR,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Merge rows of (ids, dists) (B, W) into the r closest unique entries."""
    b, w = ids.shape
    assert dists.shape == (b, w)

    pad_b = (-b) % br
    pad_w = (-w) % 128 if w > 8 else 0  # lane alignment; tiny widths left as-is
    ids_p = jnp.pad(ids.astype(jnp.int32), ((0, pad_b), (0, pad_w)),
                    constant_values=-1)
    dists_p = jnp.pad(dists.astype(jnp.float32), ((0, pad_b), (0, pad_w)),
                      constant_values=jnp.inf)
    bp, wp = ids_p.shape

    grid = (bp // br,)
    out_ids, out_dists = pl.pallas_call(
        functools.partial(_topr_merge_kernel, r=r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, wp), lambda i: (i, 0)),
            pl.BlockSpec((br, wp), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, r), lambda i: (i, 0)),
            pl.BlockSpec((br, r), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, r), jnp.int32),
            jax.ShapeDtypeStruct((bp, r), jnp.float32),
        ],
        interpret=interpret,
    )(ids_p, dists_p)
    return out_ids[:b], out_dists[:b]
