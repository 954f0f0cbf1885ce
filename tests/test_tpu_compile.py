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
from repro.kernels.matchrank import ref as mr_ref

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
    """Plan operands as ``lower_request`` emits them: rank alternatives in
    ``RANK_SLOTS`` numerator and denominator rows."""
    q = 2 * mr_ref.RANK_SLOTS
    return (
        sds((b, T_PAD, A_PAD)),
        sds((b, T_PAD), jnp.int32),
        sds((b, T_PAD)),
        sds((b, T_PAD)),
        sds((b, q, A_PAD)),
        sds((b, q)),
    )


@pytest.fixture(scope="module")
def sds(one_chip):
    return lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip
    )


def _packed_launch(sds, b, c=mr_ops.MIN_CANDIDATES):
    """The candidate launch's one packed operand: ``b`` plans and ``c``
    candidate rows a request, as ``_pack_launch`` lays them out."""
    return sds((b, mr_ops._plan_fields(T_PAD, A_PAD)[-1][2] + c), jnp.int32)


@pytest.mark.parametrize(
    "k,c",
    [
        pytest.param(1, mr_ops.MIN_CANDIDATES, id="1"),
        pytest.param(4, mr_ops.MIN_CANDIDATES, id="4"),
        pytest.param(3, 16, id="3-c16"),
    ],
)
def test_matchrank_batched_compiles(sds, k, c):
    """The tier-1 program ``select_many`` launches (B=64 requests, ``c``
    candidate columns, top-``k``)."""
    fn = functools.partial(
        mr_ops._dispatch_batched, t_pad=T_PAD, k=k, block_s=512, use_kernel=True,
        interpret=False,
    )
    _compile(fn, sds((S, A_PAD)), sds((S, A_PAD)), _packed_launch(sds, B, c))


def test_matchrank_single_compiles(sds):
    fn = functools.partial(
        mr_ops._dispatch, block_s=512, use_kernel=True, interpret=False
    )
    q = 2 * mr_ref.RANK_SLOTS
    _compile(
        fn, sds((S, A_PAD)), sds((S, A_PAD)), sds((S,)), sds((T_PAD, A_PAD)),
        sds((T_PAD,), jnp.int32), sds((T_PAD,)), sds((T_PAD,)), sds((q, A_PAD)),
        sds((q,)),
    )


def test_bwstats_compiles(sds):
    fn = functools.partial(
        bw_ops._dispatch, alpha=0.25, block_n=256, use_kernel=True, interpret=False
    )
    _compile(fn, sds((128, 4096)), sds((4096,), jnp.int32))


@pytest.mark.parametrize("b", [1, 13])
def test_matchrank_batched_alternatives_compile(sds, b):
    """The tier-1 program at batch sizes below a full flush (B = 64 is
    ``test_matchrank_batched_compiles``)."""
    fn = functools.partial(
        mr_ops._dispatch_batched, t_pad=T_PAD, k=1, block_s=512, use_kernel=True,
        interpret=False,
    )
    _compile(fn, sds((S, A_PAD)), sds((S, A_PAD)), _packed_launch(sds, b))
