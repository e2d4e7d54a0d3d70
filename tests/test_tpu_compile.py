"""The affinity kernel compiles for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with jaxlib compiles the batched
Pallas kernel for a chip that is described, not attached, at real
round-buffer buckets: V = 2048 is the kernel's stated bound, and small
pools with long queues are where lane padding makes the VMEM blocks
largest for their size.  A refused
tiling, an unaligned block or too much VMEM fails here at no chip time.
The topology is described inside a fixture, never at import, so that every
pytest-xdist worker collects the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.affinity.kernel import affinity_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A described-chip compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off here.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: no TPU compiler
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("B,T,V", [(8, 1024, 1024), (8, 2048, 2048),
                                   (1, 128, 2048), (1, 2048, 2),
                                   (8, 4096, 8), (8, 2048, 128)])
def test_affinity_kernel_compiles_for_v5e(one_chip, B, T, V):
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    args = (f32((B, T)), f32((B, T)), f32((B, T)),
            f32((B, T, V)), f32((B, T, V)),
            jax.ShapeDtypeStruct((B, T, V), jnp.int32, sharding=one_chip),
            f32((B, V)), f32((B, V)), f32((B, V)))
    fn = jax.jit(lambda *a: affinity_pallas(*a, gs_read=50.0, gs_write=30.0,
                                            bp_ms=1000.0, interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
