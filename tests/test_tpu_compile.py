"""Compile-only checks for a TPU v5e that is described, not attached.

Each test compiles one main-path program at Mixtral-8x7B widths (E=8
experts, d=4096, expert d_ff=14336, bf16) with the TPU compiler that
ships with jaxlib: the Pallas MoE kernels (Mosaic, not interpret mode)
and the float64 DES pre-work of the sharded tiers.  What the chip's
compiler refuses (tiles off the (8, 128) grid, scoped-VMEM overruns)
fails here without a chip.  Nothing runs, so nothing here says anything
about results or speed.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the test workers all
import this file.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import moe_ffn
from repro.kernels import moe_route as mr

E, D, F = 8, 4096, 14336
BF16 = jnp.bfloat16
#: one 512-token prefill group at top-2, capacity factor 1.25
G, GSZ, CAP = 1, 512, 160


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _weights(spec):
    return spec((E, D, F), BF16), spec((E, D, F), BF16), spec((E, F, D), BF16)


@pytest.fixture
def spec(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def test_moe_expert_ffn_compiles(spec):
    fn = functools.partial(moe_ffn.moe_expert_ffn, interpret=False)
    assert "tpu_custom_call" in _compile(fn, spec((E, G * CAP, D), BF16),
                                         *_weights(spec))


@pytest.mark.parametrize("gsz, cap", [(GSZ, CAP), (4, 2)],
                         ids=["prefill", "decode"])
def test_moe_expert_ffn_ragged_compiles(spec, gsz, cap):
    """A 4-token decode group leaves 2 rows per expert segment, which
    `row_block` must still round up to a legal bf16 tile."""
    def ragged(xg, mask, w1, wu, w2):
        pos, keep = mr.capacity_positions(mask, cap)
        layout = mr.grouped_layout(pos, keep, cap)
        xs = mr.grouped_dispatch(xg, layout)
        return mr.moe_expert_ffn_ragged(xs, layout, w1, wu, w2,
                                        interpret=False)

    assert "tpu_custom_call" in _compile(
        ragged, spec((G, gsz, D), BF16), spec((G, gsz, E), jnp.float32),
        *_weights(spec))


def test_capacity_dispatch_compiles(spec):
    fn = functools.partial(mr.capacity_dispatch, cap=CAP, interpret=False)
    assert "tpu_custom_call" in _compile(
        fn, spec((G, GSZ, D), BF16), spec((G, GSZ, E), jnp.int32),
        spec((G, GSZ, E), jnp.float32))


def test_capacity_combine_compiles(spec):
    fn = functools.partial(mr.capacity_combine, out_dtype=BF16,
                           interpret=False)
    assert "tpu_custom_call" in _compile(
        fn, spec((E, G, CAP, D), BF16), spec((G, GSZ, E), jnp.float32),
        spec((G, GSZ, E), jnp.int32), spec((G, GSZ, E), jnp.float32))


def test_fused_route_compiles(spec):
    fn = functools.partial(mr.fused_route, interpret=False)
    assert "tpu_custom_call" in _compile(
        fn, spec((G * GSZ, E), jnp.float32), spec((G * GSZ, E), jnp.float32))


@pytest.mark.parametrize("n_devices", [1, 4])
def test_des_prework_x64_compiles(topo, n_devices):
    """The sharded tiers' float64 pre-work at B=2048 instances of K=8
    experts, over a batch mesh of one chip and of all four."""
    from repro.distributed.sharding import BATCH_AXIS
    from repro.schedulers.sharded import _sharded_prework_fn

    b, k = 2048, 8
    mesh = Mesh(np.array(topo.devices[:n_devices]), (BATCH_AXIS,))
    rows = NamedSharding(mesh, P(BATCH_AXIS))
    mat = NamedSharding(mesh, P(BATCH_AXIS, None))
    with jax.enable_x64(True):
        args = (jax.ShapeDtypeStruct((b, k), jnp.float64, sharding=mat),
                jax.ShapeDtypeStruct((b, k), jnp.float64, sharding=mat),
                jax.ShapeDtypeStruct((b,), jnp.float64, sharding=rows),
                jax.ShapeDtypeStruct((b, k), jnp.bool_, sharding=mat))
        hlo = _sharded_prework_fn(mesh, 2).lower(*args).compile().as_text()
    assert "f64" in hlo
