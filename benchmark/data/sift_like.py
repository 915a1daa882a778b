"""SIFT-class corpus and queries, made on the device from the seed.

A copy of ``raft_tpu.random.datagen.make_sift_like`` (PR 21), kept here so
that no later PR can change the data the yardstick runs on. The arithmetic
is the original's; it runs as one jitted call (the corpus blocks under
``lax.map``) instead of a Python loop of small programs.

Shapes of ann-benchmarks' ``sift-128-euclidean``: 128-d float32 rows, low
intrinsic dimension and multi-scale local density — 2000 clusters x 16
sub-clumps, residuals in a per-cluster random 16-dim subspace (clump
offsets std 0.5, fine residuals std 0.15).

The corpus is the deployment's one base set, made from the configuration's
``corpus_seed``, as SIFT-1M is one fixed set; the run's ``--seed`` draws
its query set from the same distribution. A corpus that changed with the
run's seed changed the built IVF-PQ index's shapes (the count of split
lists), so most new seeds compiled new programs in set-up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

D, N_CLUSTERS, INTRINSIC_DIM, N_CLUMPS = 128, 2000, 16, 16
# one 50k-row block gathers a (50k, 16, 128) f32 basis temporary (~410 MB);
# a whole 1M draw would need ~8.2 GB
BLOCK = 50_000


def seed_key(seed: int):
    """A key that uses every bit of a seed up to 64 bits
    (``jax.random.key`` alone keeps only the low 32 with x64 off)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


@functools.partial(jax.jit, static_argnames=("n", "n_queries"))
def _generate(key, query_key, n: int, n_queries: int):
    kc, kb, ko, kl, kj, kz = jax.random.split(key, 6)
    centers = jax.random.uniform(kc, (N_CLUSTERS, D), jnp.float32) * 10.0
    bases = jax.random.normal(kb, (N_CLUSTERS, INTRINSIC_DIM, D), jnp.float32)
    bases = bases / jnp.linalg.norm(bases, axis=-1, keepdims=True)
    offsets = 0.5 * jax.random.normal(
        ko, (N_CLUSTERS, N_CLUMPS, INTRINSIC_DIM), jnp.float32)

    def draw(k_lab, k_clump, k_noise, count):
        labels = jax.random.randint(k_lab, (count,), 0, N_CLUSTERS)
        clump = jax.random.randint(k_clump, (count,), 0, N_CLUMPS)
        z = offsets[labels, clump] + 0.15 * jax.random.normal(
            k_noise, (count, INTRINSIC_DIM))
        return centers[labels] + jnp.einsum(
            "ni,nid->nd", z, bases[labels], precision="highest")

    blk = min(BLOCK, n)
    nb = n // blk
    blocks = jax.lax.map(
        lambda ks: draw(ks[0], ks[1], ks[2], blk),
        tuple(jax.random.split(k, nb) for k in (kl, kj, kz)))
    return (blocks.reshape(n, D),
            draw(*jax.random.split(query_key, 3), n_queries))


def generate(spec: dict, seed: int):
    """``(corpus (rows, 128) f32, queries (queries, 128) f32)`` on the
    default device, for the ``data`` section of a configuration: the
    corpus from its ``corpus_seed``, the queries from ``seed``."""
    n, nq = int(spec["rows"]), int(spec["queries"])
    if int(spec["dim"]) != D or spec["dtype"] != "float32":
        raise ValueError(f"sift_like makes {D}-d float32 rows, not "
                         f"{spec['dim']}-d {spec['dtype']}")
    if n % min(BLOCK, n):
        raise ValueError(f"rows ({n}) must be a multiple of {BLOCK} "
                         "or at most that")
    return _generate(seed_key(spec["corpus_seed"]), seed_key(seed), n=n,
                     n_queries=nq)
