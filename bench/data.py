"""Vector corpora for the benchmark, made on the device from a seed.

Each cell's corpus is a mixture of Gaussian clusters, each cluster spread
over its own random `latent_dim`-dimensional subspace of R^d, so that the
corpus has the local intrinsic dimension (LID) of the data set it stands
for, whatever its width.  Copied from the program's `sift1m-like`
generator (`src/repro/data/synthetic.py`, `vector_dataset` with
`latent_dim`), so that a later change there does not change what the
benchmark measures.

Queries are held out: they are drawn by the same generator call as the
base set, and kept out of it, as SIFT's and GIST's query sets are
descriptors of other images than the base set's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed, past 32 bits too."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """The host-side generator of one named stream of a seed."""
    return np.random.default_rng([int(seed), stream])


@functools.partial(jax.jit, static_argnames=("n", "d", "clusters", "latent_dim"))
def subspace_clusters(key, *, n: int, d: int, clusters: int, latent_dim: int,
                      cluster_std: float = 0.15):
    """(n, d) float32 points, about n/clusters per cluster.

    Cluster centres are N(0, 1) in R^d.  Each cluster draws its points on
    a random latent_dim-flat through its centre, with the total variance
    of an isotropic cluster of std `cluster_std` (cluster_std**2 * d).
    Clusters are drawn as equal blocks by one batched matmul, and a random
    permutation keeps n of the rows, so sizes stay near n/clusters and the
    order is mixed.
    """
    kc, ka, kn = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (clusters, d), jnp.float32)
    per = -(-n // clusters)
    kb, kp = jax.random.split(ka)
    basis = jnp.linalg.qr(
        jax.random.normal(kb, (clusters, d, latent_dim), jnp.float32))[0]
    z = jax.random.normal(kn, (clusters, per, latent_dim), jnp.float32)
    scale = cluster_std * (d / latent_dim) ** 0.5
    pts = centers[:, None, :] + scale * jnp.einsum(
        "cpm,cdm->cpd", z, basis, precision=jax.lax.Precision.HIGHEST)
    keep = jax.random.permutation(kp, clusters * per)[:n]
    return pts.reshape(clusters * per, d)[keep]


def corpus(config: dict, seed: int, n_queries: int, stream: int = 0):
    """(base (n, d), queries (n_queries, d)) on the device, from the seed.

    `stream` tells apart the corpora one run makes from one seed (a build
    cell builds several).  Cluster count is the configuration's points
    per cluster applied to n + n_queries.
    """
    n, d = config["n"], config["d"]
    data = config["data"]
    total = n + n_queries
    clusters = max(1, round(total / data["points_per_cluster"]))
    key = jax.random.fold_in(seed_key(seed), stream)
    pts = subspace_clusters(key, n=total, d=d, clusters=clusters,
                            latent_dim=data["latent_dim"],
                            cluster_std=data["cluster_std"])
    return pts[:n], pts[n:]
