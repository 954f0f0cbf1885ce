"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

Interpret mode hides what Mosaic refuses (block shapes off the (8, 128)
tiling, dynamic slices, layouts), so each kernel program is compiled here
for one chip of a ``v5e:2x2`` topology at the sizes the broker serves —
without a chip. The topology is described inside a fixture (never at
import) so that every test worker collects the same tests; the
persistent compilation cache is off around these compiles.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bwstats import ops as bw_ops
from repro.kernels.matchrank import ops as mr_ops
from repro.kernels.matchrank.sharded import _stage1_sharded, merge_topk_pallas

S, A_PAD, T_PAD, B = 10_240, 128, 16, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    """Compile for the described chip; → the compiled HLO text."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # a Mosaic kernel, not the interpreter
    return text


def _plan_shapes(sds, b):
    return (
        sds((b, T_PAD, A_PAD)),
        sds((b, T_PAD), jnp.int32),
        sds((b, T_PAD)),
        sds((b, T_PAD)),
        sds((b, A_PAD)),
        sds((b,)),
    )


@pytest.fixture(scope="module")
def sds(one_chip):
    return lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip
    )


@pytest.mark.parametrize("k", [1, 4])
def test_matchrank_batched_compiles(sds, k):
    """The tier-1 program ``select_many`` launches (B=64 requests)."""
    fn = functools.partial(
        mr_ops._dispatch_batched, k=k, block_s=512, use_kernel=True, interpret=False
    )
    _compile(fn, sds((S, A_PAD)), sds((S, A_PAD)), sds((B, S)), *_plan_shapes(sds, B))


def test_matchrank_single_compiles(sds):
    fn = functools.partial(
        mr_ops._dispatch, block_s=512, use_kernel=True, interpret=False
    )
    _compile(
        fn, sds((S, A_PAD)), sds((S, A_PAD)), sds((S,)), sds((T_PAD, A_PAD)),
        sds((T_PAD,), jnp.int32), sds((T_PAD,)), sds((T_PAD,)), sds((A_PAD,)),
        sds((1,)),
    )


def test_sharded_stage1_and_merge_compile(sds):
    """Per-shard kernel vmapped over G=8 shards, then the merge kernel."""
    g, s_shard, k = 8, 1536, 4

    def two_stage(*args):
        cand_s, cand_i = _stage1_sharded(
            *args, k=k, block_s=512, use_kernel=True, interpret=False
        )
        return merge_topk_pallas(cand_s, cand_i, k, interpret=False)

    text = _compile(
        two_stage, sds((g, s_shard, A_PAD)), sds((g, s_shard, A_PAD)),
        sds((g, B, s_shard)), *_plan_shapes(sds, B), sds((g,), jnp.int32),
    )
    assert text.count("tpu_custom_call") >= 2  # stage 1 and the merge


def test_bwstats_compiles(sds):
    fn = functools.partial(
        bw_ops._dispatch, alpha=0.25, block_n=256, use_kernel=True, interpret=False
    )
    _compile(fn, sds((128, 4096)), sds((4096,), jnp.int32))
