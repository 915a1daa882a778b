"""Synthetic dataset generators.

Re-design of the reference's generators (cpp/include/raft/random/make_blobs.cuh,
make_regression.cuh, multi_variable_gaussian.cuh — the latter using cusolver
potrf; here `jnp.linalg.cholesky`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.errors import expects
from .rng import as_key

__all__ = ["make_blobs", "make_regression", "make_sift_like",
           "multi_variable_gaussian"]


def make_blobs(
    n_samples: int,
    n_features: int,
    n_clusters: int = 3,
    cluster_std: float = 1.0,
    centers=None,
    center_box=(-10.0, 10.0),
    shuffle: bool = True,
    seed=0,
    dtype=jnp.float32,
):
    """Gaussian-blob clusters (reference: random/make_blobs.cuh).

    Returns ``(X (n_samples, n_features), labels (n_samples,) int32)``.
    ``centers`` may be a precomputed (n_clusters, n_features) array.
    """
    key = as_key(seed)
    kc, kl, kn, ks = jax.random.split(key, 4)
    if centers is None:
        centers = jax.random.uniform(
            kc, (n_clusters, n_features), dtype=dtype, minval=center_box[0], maxval=center_box[1]
        )
    else:
        centers = jnp.asarray(centers, dtype=dtype)
        n_clusters = centers.shape[0]
    labels = jax.random.randint(kl, (n_samples,), 0, n_clusters, dtype=jnp.int32)
    noise = jax.random.normal(kn, (n_samples, n_features), dtype=dtype) * cluster_std
    x = jnp.take(centers, labels, axis=0) + noise
    if shuffle:
        perm = jax.random.permutation(ks, n_samples)
        x, labels = x[perm], labels[perm]
    return x, labels


def make_regression(
    n_samples: int,
    n_features: int,
    n_informative: int | None = None,
    n_targets: int = 1,
    bias: float = 0.0,
    noise: float = 0.0,
    shuffle: bool = True,
    seed=0,
    dtype=jnp.float32,
):
    """Linear-model regression data (reference: random/make_regression.cuh).

    Returns ``(X, y, coef)`` with ``y = X @ coef + bias + N(0, noise)``.
    """
    n_informative = n_features if n_informative is None else min(n_informative, n_features)
    key = as_key(seed)
    kx, kw, kn, ks = jax.random.split(key, 4)
    x = jax.random.normal(kx, (n_samples, n_features), dtype=dtype)
    coef = jnp.zeros((n_features, n_targets), dtype=dtype)
    w = 100.0 * jax.random.uniform(kw, (n_informative, n_targets), dtype=dtype)
    coef = coef.at[:n_informative].set(w)
    y = x @ coef + bias
    if noise > 0:
        y = y + noise * jax.random.normal(kn, y.shape, dtype=dtype)
    if shuffle:
        perm = jax.random.permutation(ks, n_samples)
        x, y = x[perm], y[perm]
    return x, jnp.squeeze(y, axis=1) if n_targets == 1 else y, coef


def multi_variable_gaussian(rng, mean, cov, n_samples: int, dtype=jnp.float32):
    """Samples from N(mean, cov) via Cholesky (reference:
    random/multi_variable_gaussian.cuh, cusolver potrf path)."""
    mean = jnp.asarray(mean, dtype=dtype)
    cov = jnp.asarray(cov, dtype=dtype)
    expects(cov.shape == (mean.shape[0], mean.shape[0]), "cov must be (d, d)")
    chol = jnp.linalg.cholesky(cov + 1e-6 * jnp.eye(cov.shape[0], dtype=dtype))
    z = jax.random.normal(as_key(rng), (n_samples, mean.shape[0]), dtype=dtype)
    return mean[None, :] + z @ chol.T


def make_sift_like(n: int, n_queries: int, *, n_qsets: int = 1, seed=0):
    """SIFT-class (n, 128) f32 corpus plus ``n_qsets`` (n_queries, 128)
    query sets drawn from the same distribution — the shapes of
    ann-benchmarks' ``sift-128-euclidean``, generated on the device.

    Low intrinsic dimension AND multi-scale local density: 2000 clusters x
    16 sub-clumps; residuals live in a per-cluster random 16-dim subspace
    (clump offsets std 0.5, fine residuals std 0.15). Single-gaussian
    residuals would concentrate every neighbor margin at one scale (PQ's
    worst case, and unlike descriptor data); this structure gives PQ a
    coarse clump-vs-rest job with refine doing the fine ranking, as real
    SIFT-1M does (refine4 recall >= 0.95 at n_lists=1024, pq4x64, 8
    probes; MLE intrinsic dimension ~6-8). Returns ``(dataset, [queries,
    ...])``."""
    d, ncl, idim, nclump = 128, 2000, 16, 16
    keys = jax.random.split(as_key(seed), 6 + n_qsets)
    kc, kb, ko, kl, kj, kz = keys[:6]
    centers = jax.random.uniform(kc, (ncl, d), jnp.float32) * 10.0
    # per-cluster random basis (idim, d), unit rows
    bases = jax.random.normal(kb, (ncl, idim, d), jnp.float32)
    bases = bases / jnp.linalg.norm(bases, axis=-1, keepdims=True)
    offsets = 0.5 * jax.random.normal(ko, (ncl, nclump, idim), jnp.float32)

    def draw(kk_lab, kk_clump, kk_noise, count):
        labels = jax.random.randint(kk_lab, (count,), 0, ncl)
        clump = jax.random.randint(kk_clump, (count,), 0, nclump)
        z = offsets[labels, clump] + 0.15 * jax.random.normal(
            kk_noise, (count, idim))
        return centers[labels] + jnp.einsum(
            "ni,nid->nd", z, bases[labels], precision="highest")

    # chunked: a single 1M draw would gather bases[labels] into a
    # (1M, 16, 128) f32 temporary (~8.2 GB — over half of v5e HBM); 50k-row
    # blocks bound the temp to ~410 MB
    blk = 50_000
    nb = -(-n // blk)
    kls, kjs, kzs = (jax.random.split(k, nb) for k in (kl, kj, kz))
    dataset = jnp.concatenate(
        [draw(kls[i], kjs[i], kzs[i], min(blk, n - i * blk))
         for i in range(nb)])
    qsets = [draw(*jax.random.split(kk, 3), n_queries) for kk in keys[6:]]
    return dataset, qsets
