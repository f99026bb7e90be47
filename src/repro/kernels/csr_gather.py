"""HBM-reader kernel: paged CSR neighbor-list gather (paper §IV-D).

The FPGA HBM reader turns "read the neighbor list of vertex v" into AXI
burst commands against its pseudo-channel.  The TPU-native translation is a
*paged gather*: the edge array lives in HBM as fixed-size pages
(page = AXI burst), and a scalar-prefetched page table drives the BlockSpec
index_map so the Pallas pipeline issues one HBM->VMEM DMA per work item,
double-buffered across grid steps (decoupled access/execute).

This is the same indirection pattern as paged-attention block tables; the
page table for a BFS iteration is built in `ops.py` from the active
vertices' (start, degree) pairs.

Grid: (num_work_items,); each item copies one page to the output row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode


def _kernel(page_ids_ref, edges_ref, out_ref):
    del page_ids_ref  # consumed by the index_map (scalar prefetch)
    out_ref[...] = edges_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_pages(edges_paged: jax.Array, page_ids: jax.Array,
                 interpret: bool | None = None) -> jax.Array:
    """Gather pages of the edge array: out[i] = edges_paged[page_ids[i]].

    edges_paged: int32[num_pages, page]  (edge array viewed as pages)
    page_ids:    int32[m]                (page table, scalar-prefetched)
    returns:     int32[m, page]
    """
    m = page_ids.shape[0]
    _, page = edges_paged.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[pl.BlockSpec((1, page), lambda i, pids: (pids[i], 0))],
        out_specs=pl.BlockSpec((1, page), lambda i, pids: (i, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, page), jnp.int32),
        interpret=interpret_mode(interpret),
    )(page_ids, edges_paged)
