"""Pallas kernel tests (raft_tpu.ops) — run through the Pallas interpreter on
the CPU mesh; the same code lowers to Mosaic on TPU (verified on hardware,
see ops/topk.py benchmark notes)."""

import numpy as np
import pytest

from raft_tpu.ops import topk_pallas


@pytest.mark.parametrize("m,n,k", [(8, 256, 4), (16, 1000, 10), (9, 130, 64)])
def test_topk_pallas_matches_lax(rng, m, n, k):
    import jax.numpy as jnp
    from jax import lax

    if k > n:
        pytest.skip("k > n")
    x = jnp.asarray(rng.random((m, n)).astype(np.float32))
    v, i = topk_pallas(x, k, select_min=True, blk=256)
    v0, _ = lax.top_k(-x, k)
    np.testing.assert_allclose(np.asarray(v), np.asarray(-v0), atol=0)
    gathered = np.take_along_axis(np.asarray(x), np.asarray(i), axis=1)
    np.testing.assert_allclose(gathered, np.asarray(v), atol=0)


def test_topk_pallas_select_max(rng):
    import jax.numpy as jnp
    from jax import lax

    x = jnp.asarray(rng.random((5, 300)).astype(np.float32))
    v, i = topk_pallas(x, 7, select_min=False, blk=128)
    v0, _ = lax.top_k(x, 7)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v0), atol=0)


def test_topk_pallas_k_too_big(rng):
    import jax.numpy as jnp

    x = jnp.zeros((4, 300), jnp.float32)
    with pytest.raises(ValueError):
        topk_pallas(x, 257)


@pytest.mark.parametrize("m,n,k", [(4, 2000, 65), (8, 1500, 128),
                                   (4, 3000, 193), (4, 1000, 256)])
def test_topk_pallas_wide_k(rng, m, n, k):
    """64 < k <= 256 routes through the bitonic-merge running buffer
    (VERDICT r4 #5); same exactness + tie contract as lax.top_k."""
    import jax.numpy as jnp
    from jax import lax

    x = jnp.asarray(rng.random((m, n)).astype(np.float32))
    v, i = topk_pallas(x, k, select_min=True, blk=256)
    v0, i0 = lax.top_k(-x, k)
    np.testing.assert_allclose(np.asarray(v), np.asarray(-v0), atol=0)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i0))


def test_topk_pallas_wide_k_ties(rng):
    """Duplicate values across blocks: ties must resolve to the lowest
    column id, matching lax.top_k, through the bitonic merge."""
    import jax.numpy as jnp
    from jax import lax

    x = rng.integers(0, 12, (6, 2000)).astype(np.float32)  # heavy ties
    xj = jnp.asarray(x)
    v, i = topk_pallas(xj, 100, select_min=True, blk=256)
    v0, i0 = lax.top_k(-xj, 100)
    np.testing.assert_allclose(np.asarray(v), np.asarray(-v0), atol=0)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i0))


def test_topk_pallas_inf_inputs(rng):
    """Masked +inf entries (select_min) must lose to every finite entry but
    still be picked — with their real column ids — when a row has fewer than
    k finite values (the knn_merge_parts masked-slot pattern)."""
    import jax.numpy as jnp
    from jax import lax

    x = rng.random((6, 300)).astype(np.float32)
    x[0, 5:] = np.inf          # row 0: only 5 finite entries
    x[1, ::2] = np.inf
    xj = jnp.asarray(x)
    v, i = topk_pallas(xj, 8, select_min=True, blk=128)
    v0, i0 = lax.top_k(-xj, 8)
    np.testing.assert_allclose(np.asarray(v), np.asarray(-v0))
    # row 0 slots 5..7 are +inf but must carry REAL in-range column ids
    assert np.isinf(np.asarray(v)[0, 5:]).all()
    assert (np.asarray(i) >= 0).all() and (np.asarray(i) < 300).all()


@pytest.mark.slow
@pytest.mark.parametrize("wide_merge", ["half", "concat"])
def test_topk_pallas_wide_merge_forms_agree(rng, wide_merge):
    """Both wide-merge formulations — "half" (r06, every intermediate <= kh
    lanes) and "concat" (r05, kept for the chaining repro/bisect) — are the
    same network restricted to the kept half, so both must be bitwise
    lax.top_k."""
    import jax.numpy as jnp
    from jax import lax

    x = jnp.asarray(rng.random((4, 1500)).astype(np.float32))
    v, i = topk_pallas(x, 193, select_min=True, blk=256,
                       wide_merge=wide_merge)
    v0, i0 = lax.top_k(-x, 193)
    np.testing.assert_allclose(np.asarray(v), np.asarray(-v0), atol=0)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i0))


@pytest.mark.slow
def test_topk_pallas_two_wide_instances(rng):
    """The kh=256 chaining repro (VERDICT r5 #3), committed as a test: TWO
    wide-k (k > 128) kernel instances chained inside ONE jit program — the
    per-chunk + final-merge composition of ivf_pq's scan at the CAGRA
    build-chunk k = gpu_top_k + 1 = 193. The r05 toolchain failed to compile
    this on TPU (the 2*kh = 512-lane merge intermediates; r05 wide-k
    selector study); the r06 half-width merge caps every
    intermediate at kh lanes, and this test pins the composition so the
    select_k dispatch lift can never silently outlive a regression — on TPU
    it exercises the real Mosaic compile, on CPU the interpreter (numerics
    only). Shapes are the build chunk's scaled down ~16x (same kh, same
    two-instance structure)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    k = 193
    x = jnp.asarray(rng.random((8, 1024)).astype(np.float32))

    @jax.jit
    def two_instance(x):
        v1, i1 = topk_pallas(x, k, blk=512)
        pool = jnp.tile(v1, (1, 4))                     # (m, 4k) final merge
        v2, i2 = topk_pallas(pool, k, blk=512)
        return v1, i1, v2, i2

    v1, i1, v2, i2 = two_instance(x)
    v0, i0 = lax.top_k(-x, k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(-v0), atol=0)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    # the second instance re-selects over four copies of the sorted top-k:
    # its values are the first k of the ascending tile
    np.testing.assert_allclose(np.asarray(v2),
                               np.kron(np.asarray(v1)[:, :(k + 3) // 4 + 1],
                                       np.ones(4))[:, :k], atol=0)
