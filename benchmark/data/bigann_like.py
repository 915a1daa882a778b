"""BIGANN-class byte corpus and queries, made on the device from the seed.

big-ann-benchmarks' BIGANN rows are uint8 SIFT descriptors, 128-d. This
generator draws a mixture of Gaussian clusters, one for every
``ROWS_PER_CLUSTER`` rows (20 at 10M). A row is its cluster's centre,
drawn uniform in [0, 10]^128, plus a standard normal draw along each of
the cluster's ``INTRINSIC_DIM`` (32) random unit directions. Each 50k-row
block is mapped into bytes inside the jitted block map:

    byte = clip(rint(SCALE * x + OFFSET), 0, 255),  SCALE 20, OFFSET 20

Why this shape. A query's neighbours have to cross list boundaries, so
that recall answers to ``n_probes`` as IVF recall on SIFT data does. At
1,024 lists a 10M cluster spans ~51 lists. ``sift_like``'s shape (2000
clusters of tight sub-clumps, mapped to bytes) put every neighbour in the
query's home list: on a TPU v5e the IVF-PQ cell read one recall at 8, 16
and 32 probes alike (0.35 with 16 sub-clumps a cluster, 0.807 with 160).
Clusters count rows, not a fixed number, so a reduced corpus keeps the
10M corpus's local density: one cluster at 500k rows and 52 lists reads
the 10M cell's geometry. There, on the CPU, refine x4 over 4-bit
``pq_dim`` 64 read recall@10 0.84, 0.94 and 0.97 at 8, 16 and 32 probes
(0.83, 0.94, 0.97 at 1M rows and 113 lists). With 16 intrinsic
dimensions 8 probes already held 0.95 of the true neighbours, and with
24, 0.89. The intrinsic dimension is the one free constant, and it is an
assumption (``assumed.intrinsic_dim``), not a reading of BIGANN.

A coordinate's spread is sqrt(32 / 128) = 0.5 on average, 10 byte steps,
so the bytes lie in about [0, 255] with the centres at 20-220:
non-negative, as BIGANN's are. In a 500k-row draw 1.4e-4 of the entries
read 0 and 1.5e-6 read 255, at 10M 4.7e-4 and 7.9e-6: bounds on the
share the clip moves. No float32 array of the whole corpus
ever exists: one block's float rows (25.6 MB, plus its (50k, clusters x
32) operand, 128 MB at 10M) are the largest float temporaries.

The corpus and the queries come from the configuration's
``corpus_seed``, as ``bigann-10M`` is one fixed base set and
``query.public.10K`` one fixed query set; the run's ``--seed`` orders the
queries (the order the clients send them in). With the earlier
sub-clump generator, queries drawn anew from each seed moved the cell's
recall@10 between seeds by more than half its bound, every query judged
(PERF.md section 4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.data.sift_like import BLOCK, D, seed_key

ROWS_PER_CLUSTER, INTRINSIC_DIM = 500_000, 32
SCALE, OFFSET = 20.0, 20.0


def to_bytes(x):
    """The fixed affine map of float rows into uint8."""
    return jnp.clip(jnp.rint(SCALE * x + OFFSET), 0, 255).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("n", "n_queries"))
def _generate(key, n: int, n_queries: int):
    n_clusters = max(1, n // ROWS_PER_CLUSTER)
    kc, kb, kl, kz = jax.random.split(key, 4)
    centers = jax.random.uniform(kc, (n_clusters, D), jnp.float32) * 10.0
    bases = jax.random.normal(kb, (n_clusters, INTRINSIC_DIM, D), jnp.float32)
    bases = bases / jnp.linalg.norm(bases, axis=-1, keepdims=True)

    def draw(k_lab, k_z, count):
        labels = jax.random.randint(k_lab, (count,), 0, n_clusters)
        z = jax.random.normal(k_z, (count, INTRINSIC_DIM))
        # each row's coordinates in its own cluster's slot of one
        # (count, clusters * 32) operand: one matmul, no per-row basis
        onehot = jax.nn.one_hot(labels, n_clusters, dtype=jnp.float32)
        zc = (onehot[:, :, None] * z[:, None, :]).reshape(count, -1)
        return to_bytes(centers[labels] + jnp.matmul(
            zc, bases.reshape(-1, D), precision="highest"))

    blk = min(BLOCK, n)
    nb = n // blk
    blocks = jax.lax.map(
        lambda ks: draw(ks[0], ks[1], blk),
        tuple(jax.random.split(k, nb) for k in (kl, kz)))
    return (blocks.reshape(n, D),
            draw(*jax.random.split(jax.random.fold_in(key, 1), 2),
                 n_queries))


def generate(spec: dict, seed: int):
    """``(corpus (rows, 128) uint8 on the device, queries (queries, 128)
    uint8)`` for the ``data`` section of a configuration: both from its
    ``corpus_seed``, the queries in an order drawn from ``seed``."""
    n, nq = int(spec["rows"]), int(spec["queries"])
    if int(spec["dim"]) != D or spec["dtype"] != "uint8":
        raise ValueError(f"bigann_like makes {D}-d uint8 rows, not "
                         f"{spec['dim']}-d {spec['dtype']}")
    if n % min(BLOCK, n):
        raise ValueError(f"rows ({n}) must be a multiple of {BLOCK} "
                         "or at most that")
    corpus, queries = _generate(seed_key(spec["corpus_seed"]), n=n,
                                n_queries=nq)
    return corpus, queries[jax.random.permutation(seed_key(seed), nq)]
