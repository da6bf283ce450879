"""Compile the kernels of the served path for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described and not attached, and refuses what the chip would refuse (block
shapes, layouts, primitives Mosaic has no lowering for).  Each kernel is
compiled with ``interpret=False`` at the widths the engine sends it, and
its HLO must hold the Mosaic custom call.  The bank-mesh tile function is
compiled on a described 2x2 mesh.

The topology is described inside a module fixture only: only one process
at a time may load the TPU library, so nothing here touches it while the
module is imported.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n,stop,k", [
    pytest.param(n, stop, 2, id=f"{n}-{stop}")
    for n, stop in ((64, None), (128, None), (1024, None), (4096, None),
                    (1024, 16))] + [pytest.param(1024, None, 0, id="1024-k0")])
def test_colskip_kernel_compiles_for_v5e(one_chip, n, stop, k):
    from repro.kernels.colskip.kernel import sort_pallas
    x = jax.ShapeDtypeStruct((8, n), jnp.uint32, sharding=one_chip)
    hlo = _compile(lambda a: sort_pallas(a, k=k, interpret=False,
                                         stop_after=stop), x)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", [1024, 16384])
def test_radix_threshold_kernel_compiles_for_v5e(one_chip, n):
    from repro.kernels.radix_topk.kernel import threshold_pallas
    x = jax.ShapeDtypeStruct((8, n), jnp.float32, sharding=one_chip)
    hlo = _compile(lambda a: threshold_pallas(a, 8, interpret=False), x)
    assert "tpu_custom_call" in hlo


def test_bitonic_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.bitonic.kernel import sort_pallas
    x = jax.ShapeDtypeStruct((8, 1024), jnp.uint32, sharding=one_chip)
    hlo = _compile(lambda a: sort_pallas(a, interpret=False), x)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("shape,axes", [((4,), ("banks",)),
                                        ((2, 2), ("hosts", "banks"))])
def test_colskip_mesh_tile_compiles_for_v5e_2x2(topo, shape, axes):
    from repro.dist.bankmesh import sharded_tile_fn
    mesh = Mesh(np.array(topo.devices).reshape(shape), axes)
    fn = sharded_tile_fn(mesh, axes if len(axes) > 1 else axes[0],
                         32, 2, 1024, True, 1)
    x = jax.ShapeDtypeStruct((8, 1024), jnp.uint32,
                             sharding=NamedSharding(mesh, P(None, axes)))
    hlo = _compile(fn, x)
    assert "all-reduce" in hlo          # the manager's OR gates
