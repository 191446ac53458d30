"""Compile the emulator's main-path executables for a TPU v5e that is
described, not attached, at the widths the chip runs them.

The TPU compiler refuses here what it would refuse on the chip (a shape
that does not tile, a program that does not fit), so these cases guard
the chip path at no chip time. Nothing runs: a pass says the program
compiles and fits one chip's 16 GB, not that it is right or fast.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import emulator, smcprog
from repro.core.timescale import JETSON_NANO

HBM_BYTES = 16 * 10 ** 9   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described chip, with the persistent compile cache off:
    an executable compiled for a described device is written to the
    cache but cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _specs(runner, sharding):
    """Shape-only stand-ins for a runner's arguments on ``sharding``."""
    out = []
    for a in runner.avals:
        shapes = jax.eval_shape(a) if callable(a) \
            else jax.ShapeDtypeStruct(a[0], a[1])
        out.append(jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding), shapes))
    return out


def _compile_fits(runner, sharding):
    compiled = runner.jitted.lower(*_specs(runner, sharding)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used <= HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("bucket,batch", [(4096, 8), (4096, 1024)])
def test_batched_runner_compiles(one_chip, bucket, batch):
    key = emulator.compile_key(bucket, batch, JETSON_NANO, "ts", None,
                               emulator.slot_budget(bucket, bucket))
    _compile_fits(emulator._build_runner(key, False, 0), one_chip)


def test_stream_runner_compiles(one_chip):
    key = emulator.stream_compile_key(16384, 8, JETSON_NANO, "ts")
    _compile_fits(emulator._build_stream_runner(key), one_chip)


def test_policy_axis_runner_compiles(one_chip):
    bucket = 2048
    key = emulator.compile_key(
        bucket, 256, emulator._policy_rt_sys(JETSON_NANO), "ts", None,
        emulator.slot_budget(bucket, bucket), smcprog.table_bucket(8))
    _compile_fits(emulator._build_runner(key, False, 0), one_chip)


def test_filtered_runner_compiles(one_chip):
    """The tRCD sweep's dispatch of both arms: 32 lanes of 4096 with a
    shared 1 Mibit filter and the per-lane filter mask."""
    bucket = 4096
    bloom = (jnp.zeros((1 << 20) // 32, jnp.uint32), 4, 1 << 20)
    key = emulator.compile_key(bucket, 32, JETSON_NANO, "ts", bloom,
                               emulator.slot_budget(bucket, bucket))
    runner = emulator._build_runner(key, False, 0)
    assert runner.avals[-1] == ((32,), jnp.int32)   # the mask
    _compile_fits(runner, one_chip)
