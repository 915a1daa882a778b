"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e — what the chip's compiler would refuse, caught with no chip.

Nothing runs: each test lowers and compiles one kernel at a real width for
one device of a described ``v5e:2x2`` topology and asserts the kernel is in
the compiled program (``tpu_custom_call``). The topology is described inside
a module-scoped fixture, never at import time: only one process may load the
TPU library, and every test worker imports this file. Keep these tests in
this one file, so one worker holds the library.
"""

import functools

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fused_knn_f32_100k(one_chip):
    from raft_tpu.ops.fused_knn import _fused_knn_impl

    fn = functools.partial(_fused_knn_impl, keep=None, k=10, l2=True,
                           mode="f32", qt=128, nblk=4096, interpret=False)
    text = _compile_text(lambda x, q, yn: fn(x, q, yn),
                         _spec(one_chip, (100_000, 128)),
                         _spec(one_chip, (1_000, 128)),
                         _spec(one_chip, (100_000,)))
    assert "tpu_custom_call" in text


def test_cagra_hop_arena_itopk32(one_chip):
    from raft_tpu.ops.cagra_hop import cagra_hop

    m, deg, d = 1_000, 32, 128
    i32 = jnp.int32
    fn = functools.partial(cagra_hop, itopk=32, width=1)
    text = _compile_text(fn, _spec(one_chip, (m, d)),
                         _spec(one_chip, (m, 128)),
                         _spec(one_chip, (m, 128), i32),
                         _spec(one_chip, (m, 128), i32),
                         _spec(one_chip, (m, deg), i32),
                         _spec(one_chip, (m, deg, d)),
                         _spec(one_chip, (m, deg), i32))
    assert "tpu_custom_call" in text


def test_topk_pallas_k10_65536_cols(one_chip):
    from raft_tpu.ops.topk import topk_pallas

    text = _compile_text(lambda x: topk_pallas(x, 10, interpret=False),
                         _spec(one_chip, (10_000, 65_536)))
    assert "tpu_custom_call" in text
