"""Compile the reshard pack kernels for a v5e chip, without one attached.

The TPU compiler installed with JAX compiles for a described topology: these
tests lower ``execute_pack_jax_all`` at field sizes and check that Mosaic
accepts the tiling (no block over the lane/sublane rule, none out of scoped
VMEM) and that the kernel is really there (``tpu_custom_call``).  The
topology is described inside a fixture, never at import, so every pytest
worker collects the same tests and only the one running this file loads the
TPU library.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.redistribute import CompiledPlan, even_blocks, execute_pack_jax_all
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape, axis, nranks", [
    ((512, 512, 512), 0, 6),     # ragged rank count: the tail tile
    ((512, 512, 512), 1, 4),
    ((16384, 16384), 0, 6),
    ((16384, 16384), 1, 4),
])
def test_pack_compiles_for_v5e(one_chip, monkeypatch, shape, axis, nranks):
    # the backend here is the CPU; the kernel must lower for Mosaic
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    plan = CompiledPlan([((0,) * len(shape), shape)],
                        even_blocks(shape, nranks, axis=axis), shape,
                        np.float32)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda a: execute_pack_jax_all(plan, a)).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
