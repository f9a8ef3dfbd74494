"""Compile the fleet's main path for a TPU v5e without the chip.

The TPU compiler is installed on the CPU test hosts and compiles for a
described ``v5e:2x2`` topology: what Mosaic or XLA would refuse on the
chip (a block shape off the tiling, a primitive Mosaic cannot lower, a
program that does not fit 16 GB) fails here at no chip time.  Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around the
compiles (an entry written for a described chip cannot be read back).
"""

import numpy as np
import pytest

from repro.config import VMConfig

N = 128 * 128                  # the chip_smoke.py fleet
HBM_BYTES = 16 * 1024**3       # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fleet_shapes(cfg: VMConfig, sharding):
    """Stacked VMState shapes for N nodes, placed by ``sharding``."""
    import jax

    from repro.core.vm.vmstate import VMState, init_state

    one = init_state(cfg)
    return VMState(*[
        jax.ShapeDtypeStruct((N,) + x.shape, x.dtype, sharding=sharding)
        for x in one
    ])


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


def test_batched_round_one_chip(topo, no_persistent_cache):
    from jax.sharding import SingleDeviceSharding

    from repro.core.vm.fleet import FleetKernels

    cfg = VMConfig()
    S = _fleet_shapes(cfg, SingleDeviceSharding(topo.devices[0]))
    kernels = FleetKernels(cfg, executor="batched")
    compiled = kernels.round.lower(S, cfg.steps_per_slice).compile()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("obs", [False, True], ids=["plain", "obs"])
def test_vmloop_kernel(topo, no_persistent_cache, obs):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.vmloop.ref import CoreState, Layout
    from repro.kernels.vmloop.vmloop import vmloop_call

    cfg = VMConfig()
    lay = Layout(cfg)
    one_chip = SingleDeviceSharding(topo.devices[0])
    core = CoreState(**{
        f: jax.ShapeDtypeStruct((N,) + lay.shape[f], jnp.int32,
                                sharding=one_chip)
        for f in CoreState._fields
    })
    compiled = jax.jit(
        lambda c: vmloop_call(c, cfg.steps_per_slice, cfg, obs=obs)
    ).lower(core).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_sharded_round_four_chips(topo, no_persistent_cache):
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro.core.vm.fleet import FleetKernels

    cfg = VMConfig()
    mesh = Mesh(np.array(topo.devices[:4]), ("node",),
                axis_types=(AxisType.Auto,))
    S = _fleet_shapes(cfg, NamedSharding(mesh, PartitionSpec("node")))
    kernels = FleetKernels(cfg, mesh=mesh, executor="batched")
    compiled = kernels.round.lower(S, cfg.steps_per_slice).compile()
    # memory_analysis is per device: a quarter of the fleet each.
    assert _device_bytes(compiled) < HBM_BYTES
