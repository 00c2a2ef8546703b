"""Compile the planner's Pallas kernels for a described TPU v5e.

Interpret mode runs a kernel's semantics on the CPU but none of the TPU
compiler's rules: an unlowerable primitive, a block that breaks the
(8, 128) tiling or a VMEM overrun only show up when Mosaic compiles the
kernel.  The TPU compiler is installed with JAX and compiles for a chip
that is described rather than attached, so every test here compiles one
kernel — or the whole exact planning chain — at the sizes
``chip_smoke.py`` runs, for one chip of a ``v5e:2x2`` topology, and checks
that the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test collection
happens in every worker.  The persistent compilation cache is off around
these compiles — an entry compiled for a described chip cannot be read
back without one.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.probe.probe import probe_counts_pallas
from repro.kernels.rectload.rectload import jagged_loads_pallas
from repro.kernels.sat.sat import sat_pallas
from repro.kernels.sat.sat3d import sat3_pallas
from repro.rebalance import planner

T, N, P, Q = 16, 4096, 32, 32       # chip_smoke's 2D stream
T3, N3 = 4, 256                     # chip_smoke's 3D volumes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(pallas_kernels, monkeypatch):
    """The planner takes its Pallas kernels, compiled for the chip."""
    monkeypatch.setenv("JAX_PALLAS_INTERPRET", "0")


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_sat_compiles(one_chip, dtype):
    x = jax.ShapeDtypeStruct((T, N, N), dtype, sharding=one_chip)
    _compile(lambda a: sat_pallas(a), x)


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_sat3_compiles(one_chip, dtype):
    x = jax.ShapeDtypeStruct((T3, N3, N3, N3), dtype, sharding=one_chip)
    _compile(lambda a: sat3_pallas(a), x)


def test_probe_compiles_at_stripe_shape(one_chip):
    """The exact path's column probe: (P, n2+1) stripe prefixes against
    (P, k=8) candidates, vmapped over the frame stack."""
    p = jax.ShapeDtypeStruct((T, P, N + 1), jnp.int32, sharding=one_chip)
    ls = jax.ShapeDtypeStruct((T, P, 8), jnp.int32, sharding=one_chip)
    _compile(jax.vmap(lambda a, b: probe_counts_pallas(a, b, Q)), p, ls)


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_rectload_compiles(one_chip, dtype):
    g = jax.ShapeDtypeStruct((N + 1, N + 1), dtype, sharding=one_chip)
    rc = jax.ShapeDtypeStruct((P + 1,), jnp.int32, sharding=one_chip)
    cc = jax.ShapeDtypeStruct((P, Q + 1), jnp.int32, sharding=one_chip)
    _compile(lambda a, b, c: jagged_loads_pallas(a, b, c), g, rc, cc)


def test_exact_plan_frames_compiles_with_kernels(one_chip, compiled_kernels):
    """SAT -> exact JAG-PQ-OPT with the probe kernel inside its
    while_loops, the whole stream in one program, fits one chip."""
    x = jax.ShapeDtypeStruct((T, N, N), jnp.int32, sharding=one_chip)
    compiled = _compile(functools.partial(
        planner.plan_frames, P=P, m=P * Q, exact=True), x)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


def test_sharded_exact_plan_compiles_on_four_chips(topo, compiled_kernels):
    """The frame-sharded chain over all four chips of the host."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(topo.devices), ("data",))
    x = jax.ShapeDtypeStruct((T, N, N), jnp.int32,
                             sharding=NamedSharding(mesh,
                                                    PartitionSpec("data")))
    fn = planner._sharded_plan_fn(mesh, P, P * Q, 8, 8, jnp.dtype(jnp.int32),
                                  True)
    assert "tpu_custom_call" in fn.lower(x).compile().as_text()
