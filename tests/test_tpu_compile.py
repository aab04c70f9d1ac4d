"""Compile the main path's min-plus kernels for a described TPU v5e.

Interpret mode (every other kernel test) runs the kernel bodies as XLA CPU
ops, so it cannot see what Mosaic refuses: a dynamic slice of a value, a
dynamic lane-dimension slice of a ref, a block that is not (8, 128)-tiled.
Here the TPU compiler that ships with jaxlib compiles each kernel for one
chip of a ``v5e:2x2`` topology that is described, not attached — nothing
runs, so these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.  All such compiles live in
this one file so that the worker given it is the only one that loads it.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.dynamics import incremental
from repro.kernels.minplus import kernel, ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("shape,block", [((8, 256, 256), 128),
                                         ((8, 40, 40), 40)])
def test_minplus_batched_compiles(one_chip, shape, block):
    text = _compiled_text(
        lambda a, b: kernel.minplus_pallas_batched(a, b, bm=block, bn=block,
                                                   bk=block),
        one_chip, (shape, jnp.float32), (shape, jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,dtype", [(4096, jnp.float32),
                                     (4096, jnp.bfloat16),
                                     (986, jnp.float32),
                                     (4352, jnp.float32)])
def test_apsp_tiled_compiles(one_chip, n, dtype):
    """The auto tile at N=4096, at the paper's unaligned FABRIC fleet
    (N=986 = 17 sites x 58 nodes), and at 4352 = 17 x 256, a rebuild's
    live-slot size, through the ``ops`` entry point."""
    text = _compiled_text(
        lambda d: ops.apsp_tiled(d, force_kernel=True, interpret=False),
        one_chip, ((n, n), dtype))
    assert "tpu_custom_call" in text


def test_live_slot_gather_and_scatter_compile_without_a_sort(one_chip):
    """The rebuild's gather of 4352 live slots out of 8192, and its way
    back, at the real sizes: the way back is a gather by the inverse index
    (a 2-D scatter of the m^2 entries sorts them, and took 22 s to
    compile)."""
    c, m = 8192, 4352
    for fn, shapes in [(incremental._gather_live,
                        [((c, c), jnp.float32), ((m,), jnp.int32)]),
                       (incremental._scatter_live,
                        [((1, m, m), jnp.float32), ((c,), jnp.int32)])]:
        text = _compiled_text(fn, one_chip, *shapes)
        assert " gather(" in text and " sort(" not in text


def test_column_read_compiles_to_one_slice_of_d(one_chip):
    """A route's read of ``D[:, j]`` at capacity 8192, ``j`` traced: one
    dynamic slice of a (8192, 1) column, with no copy of the 256 MiB
    matrix, and an output of the column alone."""
    c = 8192
    compiled = jax.jit(incremental._column).lower(
        jax.ShapeDtypeStruct((c, c), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert f"f32[{c},1]" in text and "dynamic-slice(" in text
    assert not re.search(rf"= f32\[{c},{c}\]\S* copy\(", text)
    mem = compiled.memory_analysis()
    assert (mem.output_size_in_bytes, mem.temp_size_in_bytes) == (c * 4, 0)
