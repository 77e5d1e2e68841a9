"""Dispatching wrappers for the Pallas kernels.

Backend policy:
  * "pallas"    — real pl.pallas_call lowering; needs a TPU.  Selecting it
                  anywhere else raises at the first kernel call: nothing
                  quietly swaps in another backend.
  * "interpret" — pallas_call(interpret=True): executes the kernel bodies
                  in Python on any backend; runs only when asked for by
                  name (the CPU parity suites and the CI interpret leg).
  * "ref"       — pure-jnp oracle; the fast path on CPU (XLA:CPU) and the
                  numerical ground truth.  "xla" is accepted as an alias.
  * "auto"      — pallas on TPU, ref elsewhere.

Selection: `set_backend()` at runtime, or the REPRO_KERNEL_BACKEND
environment variable at import time (see README.md §Backend selection).
"""
from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.gather_l2 import gather_sqdist_pallas
from repro.kernels.pairwise_l2 import pairwise_sqdist_pallas, rowwise_sqdist_pallas
from repro.kernels.rng_round import rng_round_pallas
from repro.kernels.search_expand import search_expand_pallas
from repro.kernels.topr_merge import topr_merge_pallas

_VALID = ("auto", "pallas", "interpret", "ref", "xla")


def _parts(x):
    """(data, scale, offset) of a dataset operand.

    Every distance entry point accepts either a plain (N, D) array or a
    `core.vecstore.VectorStore` (the precision ladder, DESIGN.md §8).
    Duck-typed on the store's field names rather than an isinstance so this
    module needs no import from the core package (kernels sit below core
    in the layering).
    """
    if hasattr(x, "scale") and hasattr(x, "data"):
        return x.data, x.scale, x.offset
    return x, None, None


def _normalize(backend: str) -> str:
    assert backend in _VALID, f"backend must be one of {_VALID}, got {backend!r}"
    return "ref" if backend == "xla" else backend


_BACKEND = _normalize(os.environ.get("REPRO_KERNEL_BACKEND", "auto"))


def set_backend(backend: str) -> None:
    global _BACKEND
    _BACKEND = _normalize(backend)


def get_backend() -> str:
    if _BACKEND != "auto":
        return _BACKEND
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@contextlib.contextmanager
def backend(name: str):
    """Scoped backend override (restores the previous selection on exit)."""
    global _BACKEND
    prev = _BACKEND
    set_backend(name)
    try:
        yield
    finally:
        _BACKEND = prev


def effective_backend() -> str:
    """The backend that will actually execute.

    "pallas" without a TPU raises instead of degrading: a run that asked
    for the device kernels must not report numbers from another path.
    """
    b = get_backend()
    if b == "pallas" and jax.default_backend() != "tpu":
        raise RuntimeError(
            "kernel backend 'pallas' needs a TPU, but JAX's default backend "
            f"is {jax.default_backend()!r}; select 'interpret' (the kernel "
            "bodies in Python) or 'ref' (plain jnp) by name")
    return b


def _interpret() -> bool:
    return effective_backend() == "interpret"


def pairwise_sqdist(x, y) -> jnp.ndarray:
    """(M,D) x (N,D) -> (M,N) squared L2, fp32.

    Either side may be a VectorStore (fused dequant in the kernel tiles).
    """
    xd, xs, xo = _parts(x)
    yd, ys, yo = _parts(y)
    if get_backend() == "ref":
        return _ref.pairwise_sqdist_ref(xd, yd, xs, xo, ys, yo)
    return pairwise_sqdist_pallas(xd, yd, xs, xo, ys, yo,
                                  interpret=_interpret())


def rowwise_sqdist(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """(M,D) x (M,D) -> (M,) squared L2 of corresponding rows, fp32."""
    if get_backend() == "ref":
        return _ref.rowwise_sqdist_ref(x, y)
    return rowwise_sqdist_pallas(x, y, interpret=_interpret())


def topr_merge(ids: jnp.ndarray, dists: jnp.ndarray, r: int):
    """(B,W) candidate rows -> (B,r) closest unique entries. See ref.topr_merge_ref."""
    if get_backend() == "ref":
        return _ref.topr_merge_ref(ids, dists, r)
    return topr_merge_pallas(ids, dists, r, interpret=_interpret())


def search_expand(x, queries, nbrs, table, valid=None, vwords=None,
                  fwords=None, bitmap=None):
    """Fused beam-search expansion step:
    (ids, dists, fresh[, allowed][, bitmap]).

    See ref.search_expand_ref for semantics; the pallas path fuses the
    neighbor-vector gather, query->neighbor distances, the visited probe,
    and the optional tombstone-validity probe into one VMEM-resident
    pass (kernels/search_expand.py).  The visited set is either the
    hashed `table` or, with `table=None`, the dense `bitmap`, which comes
    back updated as the last output.  `valid` is the dynamic index's (N,)
    vertex-validity mask (None = all live, the static-index path).  `x`
    may be a VectorStore (fused dequant on the row DMA).  `vwords`/`fwords`
    are the optional filtered-search predicate (core/labels.py): packed
    (N, W) vertex label words + (Q, W) query allowed words; when given,
    an `allowed` output is appended (route-through semantics).
    """
    xd, xs, xo = _parts(x)
    if get_backend() == "ref":
        return _ref.search_expand_ref(xd, queries, nbrs, table, valid,
                                      xs, xo, vwords, fwords, bitmap)
    return search_expand_pallas(xd, queries, nbrs, table, valid, xs, xo,
                                vwords, fwords, bitmap,
                                interpret=_interpret())


def rng_propagation_round(x, ids, dists, si, sj):
    """Fused disordered propagation round: (dst, src, dij, kill).

    See ref.rng_round_ref for semantics; the pallas path fuses the
    neighbor-vector gather, pair distances, RNG criterion, and kill-mask
    emission into one VMEM-resident pass (kernels/rng_round.py).  `x` may
    be a VectorStore (fused dequant on the row DMA).
    """
    xd, xs, xo = _parts(x)
    if get_backend() == "ref":
        return _ref.rng_round_ref(xd, ids, dists, si, sj, xs, xo)
    return rng_round_pallas(xd, ids, dists, si, sj, xs, xo,
                            interpret=_interpret())


def gather_sqdist(x, ni, nj) -> jnp.ndarray:
    """d(x[ni[m]], x[nj[m]]) for m in [0, M) -> (M,) fp32.

    See ref.gather_sqdist_ref; the pallas path (kernels/gather_l2.py) DMAs
    the two rows per step straight into VMEM — no materialized (M, D)
    gathers.  `x` may be a VectorStore (fused dequant on the row DMA).
    """
    xd, xs, xo = _parts(x)
    if get_backend() == "ref":
        return _ref.gather_sqdist_ref(xd, ni, nj, xs, xo)
    return gather_sqdist_pallas(xd, ni, nj, xs, xo, interpret=_interpret())
