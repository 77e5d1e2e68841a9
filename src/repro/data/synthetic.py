"""Synthetic datasets: vector corpora (ANN benchmarks) + LM token streams.

Vector datasets model the paper's benchmark families at reduced scale:
  * "sift-like"  — clustered, moderate dimension (SIFT1M: D=128)
  * "sift1m-like" — SIFT1M's D and local intrinsic dimension, for runs at
    SIFT1M's scale (below)
  * "deep-like"  — unit-norm embeddings (DEEP1M: D=96)
  * "gist-like"  — high dimension (GIST1M: D=960)

Clustered Gaussian mixtures reproduce the local-neighborhood structure that
makes graph ANN interesting (uniform data has no cluster structure and makes
every method look alike).

The small presets draw isotropic clusters; the tests and reduced-scale
benchmarks are calibrated on them.  At SIFT1M's scale (about 7,800 points
per cluster) an isotropic 128-D cluster has a local intrinsic dimension
(LID, the maximum-likelihood estimate over 100 nearest neighbours) of
about 48, and a point's 10th neighbour lies only ~5% farther than its
first: far harder than SIFT, whose LID ANN-Benchmarks (Aumüller et al.,
Information Systems 2020) puts at about 22.  "sift1m-like" spreads each
cluster over a 32-dimensional subspace instead, which measures about 21 at
n = 1,000,000 by the same estimate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def vector_dataset(
    key: jax.Array,
    n: int,
    d: int,
    n_clusters: int = 64,
    cluster_std: float = 0.15,
    normalize: bool = False,
    dtype=jnp.float32,
    latent_dim: int | None = None,
) -> jnp.ndarray:
    """Clustered Gaussian mixture, roughly unit-scale coordinates.

    latent_dim=None draws isotropic clusters: their intrinsic dimension is
    d, so within a cluster every point is nearly equidistant from every
    other.  latent_dim=m spreads each cluster over its own random
    m-dimensional subspace with the same total variance (cluster_std**2 * d),
    so the local intrinsic dimension is about m whatever d is.
    """
    kc, ka, kn = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (n_clusters, d), jnp.float32)
    if latent_dim is None:
        assign = jax.random.randint(ka, (n,), 0, n_clusters)
        pts = centers[assign] + cluster_std * jax.random.normal(
            kn, (n, d), jnp.float32)
    else:
        pts = _subspace_clusters(ka, kn, centers, n, latent_dim, cluster_std)
    if normalize:
        pts = pts / jnp.linalg.norm(pts, axis=-1, keepdims=True)
    return pts.astype(dtype)


def _subspace_clusters(ka, kn, centers, n, m, cluster_std):
    """n points, about n/C per cluster, each cluster on a random m-flat.

    Clusters are drawn as equal blocks (a batched (C, per, m) @ (C, m, d)
    matmul, never an (n, m, d) gather) and a random permutation keeps n of
    the C * per rows, so cluster sizes stay near n/C and their order mixed.
    """
    c, d = centers.shape
    per = -(-n // c)
    kb, kp = jax.random.split(ka)
    # per cluster, m orthonormal columns in R^d
    basis = jnp.linalg.qr(jax.random.normal(kb, (c, d, m), jnp.float32))[0]
    z = jax.random.normal(kn, (c, per, m), jnp.float32)
    scale = cluster_std * (d / m) ** 0.5
    pts = centers[:, None, :] + scale * jnp.einsum(
        "cpm,cdm->cpd", z, basis, precision=jax.lax.Precision.HIGHEST)
    keep = jax.random.permutation(kp, c * per)[:n]
    return pts.reshape(c * per, d)[keep]


def queries_from(key: jax.Array, x: jnp.ndarray, q: int, noise: float = 0.05):
    """Queries near dataset points (the realistic ANN query regime)."""
    ki, kn = jax.random.split(key)
    idx = jax.random.randint(ki, (q,), 0, x.shape[0])
    return x[idx] + noise * jax.random.normal(kn, (q, x.shape[1]), x.dtype)


DATASET_PRESETS = {
    # name: (d, n_clusters, normalize, latent_dim)
    "sift-like": (128, 128, False, None),
    "sift1m-like": (128, 128, False, 32),
    "deep-like": (96, 128, True, None),
    "gist-like": (960, 64, False, None),
    "tiny": (16, 16, False, None),
}


def make_preset(key: jax.Array, name: str, n: int) -> jnp.ndarray:
    d, ncl, norm, latent = DATASET_PRESETS[name]
    return vector_dataset(key, n, d, n_clusters=ncl, normalize=norm,
                          latent_dim=latent)


def token_stream(key: jax.Array, batch: int, seq: int, vocab: int) -> jnp.ndarray:
    """Zipf-ish synthetic token ids for LM training."""
    u = jax.random.uniform(key, (batch, seq), jnp.float32, 1e-6, 1.0)
    ranks = jnp.floor(jnp.exp(u * jnp.log(float(vocab)))) - 1.0
    return jnp.clip(ranks, 0, vocab - 1).astype(jnp.int32)
