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
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.affinity import ops
from repro.kernels.affinity.kernel import affinity_pallas

BUCKETS = [(8, 1024, 1024), (8, 2048, 2048), (1, 128, 2048), (1, 2048, 2),
           (8, 4096, 8), (8, 2048, 128)]


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


def _shapes(one_chip, B, T, V):
    """The nine round-buffer arrays of a ``(B, T, V)`` bucket."""
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    return (f32((B, T)), f32((B, T)), f32((B, T)),
            f32((B, T, V)), f32((B, T, V)),
            jax.ShapeDtypeStruct((B, T, V), jnp.int32, sharding=one_chip),
            f32((B, V)), f32((B, V)), f32((B, V)))


def _live(one_chip, B, T, V):
    """A round's ``int32 [B, T + V]`` live mask."""
    return jax.ShapeDtypeStruct((B, T + V), jnp.int32, sharding=one_chip)


@pytest.mark.parametrize("B,T,V", BUCKETS)
def test_affinity_kernel_compiles_for_v5e(one_chip, B, T, V):
    fn = jax.jit(lambda *a: affinity_pallas(*a, gs_read=50.0, gs_write=30.0,
                                            bp_ms=1000.0, interpret=False))
    compiled = fn.lower(*_shapes(one_chip, B, T, V),
                        _live(one_chip, B, T, V)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,T,V", BUCKETS)
def test_packed_affinity_batch_compiles_for_v5e(one_chip, monkeypatch,
                                                B, T, V):
    """The program ``multi_cycle`` dispatches: the kernel, with the live
    mask split into a row and a column operand, and the packing of its four
    outputs into one ``int32 [4, B, T]`` array."""
    monkeypatch.setattr(ops, "affinity_pallas",
                        partial(affinity_pallas, interpret=False))
    fn = jax.jit(lambda *a: ops._affinity_batch_impl(
        *a, 50.0, 30.0, 1000.0, use_pallas=True, packed=True))
    compiled = fn.lower(*_shapes(one_chip, B, T, V),
                        _live(one_chip, B, T, V)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert (out.shape, out.dtype) == ((4, B, T), jnp.int32)


@pytest.mark.parametrize("B,T,V", BUCKETS)
def test_upload_aliases_its_transfers_on_v5e(one_chip, B, T, V):
    """An auction's upload of its nine arrays compiles to no device work:
    each output is its donated input's buffer, so nothing is copied."""
    compiled = ops._upload_donated.lower(*_shapes(one_chip, B, T, V)).compile()
    text = compiled.as_text()
    assert text.count("input_output_alias") == 1
    assert all(f"{{{i}}}: ({i}, {{}}" in text for i in range(9))
    assert "copy" not in text.split("ENTRY", 1)[1]
