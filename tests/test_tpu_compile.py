"""Compiles of the main path for one TPU v5e chip, without the chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described but not attached, so these tests catch what Mosaic or XLA:TPU
would refuse (an unsupported layout, an i64 index, an unlowerable
primitive) before any run on a chip.  They compile at deployment sizes
and run nothing.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and under
several test workers an import-time call would fail in every worker but
one.  Where no topology can be described, the fixture skips.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROWS = 262_144          # the serving phase's resident table
KERNEL_ROWS = 65_536
BLOCK_N = 1024
BATCH = 256
SETTLES = 256


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# the kernels' index arithmetic must stay int32 with jax_enable_x64 on:
# Mosaic refuses 64-bit integers even around f32 operands
x64_modes = pytest.mark.parametrize("x64", [False, True],
                                    ids=["x64_off", "x64_on"])


@pytest.mark.parametrize("use_lower_bound", [False, True],
                         ids=["mean", "lower_bound"])
def test_service_tick_compiles(one_chip, use_lower_bound):
    """The jitted XLA tick the frontend dispatches: f32 table, a
    256-request batch, 256 settles, the drift step on."""
    from repro.core.online import ServiceState, _tick

    s = functools.partial(_spec, one_chip)
    f, i = jnp.float32, jnp.int32
    state = ServiceState(post=s((ROWS, 2), f), rowcfg=s((ROWS, 3), f),
                         flags=s((ROWS, 2), i), roll=s((ROWS, 6), i),
                         tel=s((4096, 10), f))
    compiled = _tick.lower(
        state, s((), f), s((BATCH,), i), s((BATCH,), i), s((BATCH, 7), f),
        s((0, 1), f), s((0,), i), s((SETTLES,), i), s((SETTLES,), f),
        s((), i), s((9,), i), use_lower_bound=use_lower_bound,
        check_drift=True, use_rollout=False, use_beam=False).compile()
    assert compiled.memory_analysis() is not None


@x64_modes
def test_betaincinv_kernel_compiles(one_chip, x64):
    from repro.kernels.betaincinv_pallas import betaincinv_kernel_call

    v = _spec(one_chip, (KERNEL_ROWS,), jnp.float32)
    fn = jax.jit(functools.partial(betaincinv_kernel_call, block_n=BLOCK_N,
                                   interpret=False))
    with jax.enable_x64(x64):
        compiled = fn.lower(v, v, v).compile()
    assert "tpu_custom_call" in compiled.as_text()


@x64_modes
@pytest.mark.parametrize("use_lower_bound", [False, True],
                         ids=["mean", "lower_bound"])
def test_online_tick_kernel_compiles(one_chip, x64, use_lower_bound):
    from repro.kernels.online_tick import online_tick_kernel_call

    s = functools.partial(_spec, one_chip)
    f, i = jnp.float32, jnp.int32
    fn = jax.jit(functools.partial(
        online_tick_kernel_call, use_lower_bound=use_lower_bound,
        check_drift=True, block_n=BLOCK_N, interpret=False))
    with jax.enable_x64(x64):
        compiled = fn.lower(
            s((KERNEL_ROWS, 2), f), s((KERNEL_ROWS, 3), f),
            s((KERNEL_ROWS, 2), i), s((), f), s((BATCH,), i),
            s((BATCH, 7), f), s((SETTLES,), i), s((SETTLES,), f),
            s((), i)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@x64_modes
def test_replay_grid_kernel_compiles(one_chip, x64):
    from repro.kernels.replay_grid import replay_grid_kernel_call

    s = functools.partial(_spec, one_chip)
    v = s((KERNEL_ROWS,), jnp.float32)
    fn = jax.jit(functools.partial(replay_grid_kernel_call, rho=0.5,
                                   interpret=False))
    with jax.enable_x64(x64):
        compiled = fn.lower(v, v, v, s((7,), jnp.float32),
                            s((5,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
