"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs.  Each test lowers one kernel at real widths for one chip of
a described ``v5e:2x2`` and compiles it with the TPU compiler, which
refuses what the interpreter accepts: scalars stored to VMEM, whole edge
arrays in SMEM, blocks beyond VMEM.  The topology is described inside a
fixture, never at import, so every test worker collects the same tests
and only the worker running this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.bitmap_update import bitmap_update, bitmap_update_batch
from repro.kernels.msbfs_propagate import (msbfs_propagate_planes,
                                           msbfs_propagate_planes_tiled)

RMAT20_ROWS = 1 << 20
RMAT20_STREAM = 17_825_792     # budgeted pull stream of an rmat20-16 level


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the cache
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        mp.undo()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)


def _compile(fn, *args, **kw):
    compiled = jax.jit(lambda *a: fn(*a, interpret=False, **kw)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # a kernel, compiled
    return compiled.memory_analysis()


@pytest.mark.parametrize("nw", [1, 4])
def test_whole_vmem_propagate_compiles(shape, nw):
    """The largest graph the plan keeps whole in VMEM, with a 2^20-edge
    list streamed through SMEM in chunks."""
    n = 4095
    assert not ops.propagate_plan(n, nw)["tiled"]
    assert ops.propagate_plan(n + 1, nw)["tiled"]
    m = 1 << 20
    rows = shape((n + 1, nw), jnp.uint32)
    edges = shape((m,), jnp.int32)
    _compile(msbfs_propagate_planes, rows, rows, edges, edges,
             block_edges=ops._auto_block_edges(m, nw))


@pytest.mark.parametrize("nw", [1, 4])
def test_tiled_propagate_compiles_at_rmat20(shape, nw):
    """rmat20 rows and a full pull level's stream, at the plan's tile and
    chunk sizes.  The flat SMEM message chunks keep the stream unpadded:
    a ``[L, nw]`` block padded it to 128 lanes, 10.7 GB of temporaries."""
    plan = ops.propagate_plan(RMAT20_ROWS, nw)
    assert plan["tiled"]
    block = ops._auto_block_edges(RMAT20_STREAM, nw)
    chunks = RMAT20_STREAM // block
    mem = _compile(
        msbfs_propagate_planes_tiled,
        shape((RMAT20_ROWS, nw), jnp.uint32),
        shape((chunks * block * nw,), jnp.uint32),
        shape((chunks * block,), jnp.int32),
        shape((chunks,), jnp.int32),
        tile_rows=plan["tile_rows"], block_edges=block)
    assert mem.temp_size_in_bytes < 4 * 2**30


@pytest.mark.parametrize("batched", [False, True])
def test_p3_update_compiles(shape, batched):
    """The fused P3 kernels over 4M-bit planes (rmat22 vertices), four
    plane words batched."""
    if batched:
        words = shape((4, 32768, 128), jnp.uint32)
        _compile(bitmap_update_batch, words, words, block_rows=16)
    else:
        words = shape((32768, 128), jnp.uint32)
        _compile(bitmap_update, words, words, block_rows=16)
