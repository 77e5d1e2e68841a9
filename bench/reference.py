"""The plain reference: exact k nearest neighbours under squared L2.

Straight `jax.numpy`, independent of the program under test: it imports
nothing of `repro` and takes nothing the program made, only the corpus
and the queries.  Distances for the top-k search are the matmul form
|q|^2 + |x|^2 - 2 q.x at HIGHEST precision (on a TPU, a DEFAULT-precision
float32 matmul runs one bfloat16 pass); the distances a comparison reads
back are the subtract-square-sum form, which has no cancellation.
Work is chunked over queries, so a reference of thousands of queries
fits beside a corpus of a million rows.

`precision="bfloat16"` computes the same reference with bfloat16 inputs
(float32 accumulation): the control, one step below the float32 that the
configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16")


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _knn_chunk(x, q, *, k: int, precision: str):
    if precision == "bfloat16":
        x, q = x.astype(jnp.bfloat16), q.astype(jnp.bfloat16)
        mm = jax.lax.Precision.DEFAULT
    else:
        mm = jax.lax.Precision.HIGHEST
    xx = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=1)
    qq = jnp.sum(jnp.square(q.astype(jnp.float32)), axis=1)
    dot = jnp.matmul(q, x.T, precision=mm, preferred_element_type=jnp.float32)
    d = qq[:, None] + xx[None, :] - 2.0 * dot
    neg, ids = jax.lax.top_k(-d, k)
    return ids.astype(jnp.int32), jnp.maximum(-neg, 0.0)


def knn(x, queries, k: int, *, precision: str = "float32", chunk: int = 256):
    """-> (ids (Q, k) int32, sqdists (Q, k) float32), numpy, nearest first."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    queries = np.asarray(queries, np.float32)
    ids, dists = [], []
    for lo in range(0, len(queries), chunk):
        q = queries[lo:lo + chunk]
        pad = chunk - len(q)          # one compiled shape for every chunk
        q = np.concatenate([q, np.repeat(q[:1], pad, axis=0)]) if pad else q
        i, d = _knn_chunk(x, jnp.asarray(q), k=k, precision=precision)
        ids.append(np.asarray(i)[:chunk - pad])
        dists.append(np.asarray(d)[:chunk - pad])
    return np.concatenate(ids), np.concatenate(dists)


@jax.jit
def _pair_sqdist(a, b):
    diff = a - b
    return jnp.sum(diff * diff, axis=-1)


def pair_sqdist(x, rows_a, rows_b, chunk: int = 1 << 16):
    """d(x[rows_a[m]] or rows_a[m], x[rows_b[m]]) for M pairs -> (M,) numpy.

    `rows_a` is an (M,) index array into x, or an (M, D) array of vectors
    (queries); `rows_b` indexes x.  Chunked over M in one compiled shape.
    """
    rows_b = np.asarray(rows_b, np.int64)
    vec_a = np.ndim(rows_a) == 2
    a_all = np.asarray(rows_a, np.float32 if vec_a else np.int64)
    m = len(rows_b)
    chunk = min(chunk, max(1024, 1 << max(0, m - 1).bit_length()))
    out = np.empty(m, np.float32)
    for lo in range(0, m, chunk):
        hi = min(m, lo + chunk)
        pad = chunk - (hi - lo)
        ib = np.pad(rows_b[lo:hi], (0, pad))
        a = np.pad(a_all[lo:hi], ((0, pad),) + ((0, 0),) * (a_all.ndim - 1))
        a = jnp.asarray(a) if vec_a else x[jnp.asarray(a)]
        out[lo:hi] = np.asarray(_pair_sqdist(a, x[jnp.asarray(ib)]))[:hi - lo]
    return out
