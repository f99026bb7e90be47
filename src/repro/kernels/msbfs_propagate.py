"""Fused P2->P3 MS-BFS propagate kernel (paper §IV-C, batched).

The FPGA pipeline streams whole 256/512-bit frontier words per HBM beat:
P2 reads the packed source-mask word of each gathered edge's endpoint, P3
ORs it into the candidate word of the edge's target and commits
``next |= cand & ~visited`` — the plane state never exists in unpacked
(one-byte-per-bit) form.  This kernel is the TPU analogue for the MS-BFS
engines: one pass over the budgeted edge list that

    cand[tgt[e]] |= frontier[src[e]]          (gather + scatter-OR, P2)
    new           = cand & ~seen              (P3 result writing)
    seen'         = seen | new
    count        += popcount(new)             (Scheduler stats, for free)

with no ``unpack_rows``, no ``[budget, B]`` bool message array and no
``[n_pad+1, nb]`` bool scatter buffer — the uint32 plane words are the only
currency (the win GraphScale/ScalaBFS get from packed BRAM bitmaps).

Two layouts share the kernel body structure:

* ``msbfs_propagate_planes`` — the whole-VMEM variant: the frontier/seen/
  candidate plane arrays live whole in VMEM across the 1-D grid over edge
  chunks (the output BlockSpecs map every grid step to block (0, 0), so
  the accumulator persists between steps on TPU's sequential grid), and
  each step streams one chunk of the ``src``/``tgt`` index arrays into
  SMEM, where the per-edge loop reads them as scalars.  Each chunk runs a
  fori_loop of read-modify-write row updates — the per-edge loop is the
  literal analogue of the PE's one-edge-per-cycle P2 stage.  The last
  grid step applies P3 in place.  VMEM bound: the 4 plane arrays plus
  P3's values, whose ``nw``-word rows Mosaic pads to 128 lanes, so only
  small graphs fit (``ops.propagate_plan`` sizes it).

* ``msbfs_propagate_planes_tiled`` — the row-partitioned variant for
  HBM-scale graphs (the software analogue of ScalaBFS's 32 pseudo-
  channels each feeding the PEs only their own vertex partition).  Vertex
  rows are cut into VMEM-sized tiles; the caller pre-buckets the budgeted
  edge list by target tile (``ops._bucket_edges_by_tile``) and pre-gathers
  each edge's frontier word into a message stream, so the kernel never
  holds the frontier: per grid step it sees ONE seen/candidate tile plus
  one ``block_edges``-sized chunk of that tile's message words and target
  rows, both streamed flat into SMEM (a ``[block_edges, nw]`` VMEM block
  would pad each edge's row to 128 lanes, in HBM as well).  The
  ``chunk_tile`` scalar-prefetch array drives the BlockSpec index_maps —
  consecutive chunks of the same tile revisit the same output block, so
  the candidate accumulator persists across a tile's chunk run exactly
  like the whole-VMEM grid, while Pallas's pipeline double-buffers the
  streamed chunks against it.  P3 fires once per tile, at its last chunk.

Both kernels write their discovery popcount to a ``(1, 1)`` SMEM block:
Mosaic stores no scalars to VMEM.

Interpret mode follows the backend (``repro.kernels.mode``): compiled on
a TPU, the Pallas interpreter elsewhere.  Under the interpreter both
kernels swap the per-edge RMW loop for a one-call vectorized chunk
scatter with identical semantics (``_chunk_scatter``) — the emulator
traces every loop iteration, which serializes graph500-class edge streams
into minutes; the sequential loop remains the compiled-TPU body (force
either with ``vector_scatter=``).

The pure-jnp oracle with identical semantics is
``repro.core.bitmap._scatter_or_rows`` (see ``kernels.ref``); callers
invoke these through ``repro.kernels.ops.msbfs_propagate`` /
``ops.msbfs_propagate_msgs``, which append pad rows, bucket the edge
list and auto-select the variant by plane-array footprint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode

# Cross-plane merge ops for the scatter accumulation (the vertex-program
# ``combine``).  "or" is the bit-plane merge every shipped program uses;
# "max" is the payload-plane hook (e.g. per-plane uint32 priorities) —
# identical to "or" on single-bit planes, different on multi-bit words.
# Both accumulate from the same zero identity, and P3 keeps bitmask
# semantics (new = cand & ~seen) either way.
_COMBINE = {
    "or": lambda a, b: a | b,
    "max": jnp.maximum,
}


def _chunk_scatter(acc, rows, msgs, op: str):
    """Vectorized scatter-combine of one edge chunk (interpret mode).

    The per-edge RMW fori_loop is the TPU story — one edge per cycle
    through a resident VMEM tile, the literal P2 stage.  Under the
    interpret emulator every iteration becomes a traced dynamic-slice
    triple, so a 16M-edge pull level at rmat20 scale serializes into
    minutes of emulation.  jnp has one-call equivalents with identical
    semantics (duplicate rows combine, OOR rows drop): the bit-plane
    decomposed scatter of the ``bitmap._scatter_or_rows`` oracle for
    "or", ``at[].max`` directly for "max" — interpret mode runs those.
    """
    rows = jnp.where(rows < 0, acc.shape[0], rows)   # drop, never wrap
    if op == "max":
        return acc.at[rows].max(msgs, mode="drop")
    from repro.core import bitmap    # deferred: core imports the kernels
    return bitmap._scatter_or_rows(acc, rows, msgs)




def _kernel(src_ref, tgt_ref, frontier_ref, seen_ref, new_ref, vout_ref,
            cnt_ref, *, block_edges: int, op: str, vector_scatter: bool):
    combine = _COMBINE[op]
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        new_ref[...] = jnp.zeros_like(new_ref[...])

    if vector_scatter:
        new_ref[...] = _chunk_scatter(new_ref[...], tgt_ref[...],
                                      frontier_ref[...][src_ref[...]], op)
    else:
        def body(i, carry):
            s = src_ref[i]
            t = tgt_ref[i]
            msg = frontier_ref[pl.ds(s, 1), :]
            new_ref[pl.ds(t, 1), :] = combine(new_ref[pl.ds(t, 1), :], msg)
            return carry

        jax.lax.fori_loop(0, block_edges, body, 0)

    @pl.when(step == pl.num_programs(0) - 1)
    def _p3():
        cand = new_ref[...]
        seen = seen_ref[...]
        nf = cand & ~seen
        new_ref[...] = nf
        vout_ref[...] = seen | nf
        cnt_ref[0, 0] = jnp.sum(jax.lax.population_count(nf)
                                .astype(jnp.int32))


def _edge_block(block_edges: int, index_map):
    """One chunk of a flat per-edge array, streamed into SMEM."""
    return pl.BlockSpec((block_edges,), index_map,
                        memory_space=pltpu.SMEM)


def _count_block(index_map):
    return pl.BlockSpec((1, 1), index_map, memory_space=pltpu.SMEM)


@functools.partial(jax.jit,
                   static_argnames=("block_edges", "interpret", "op",
                                    "vector_scatter"))
def msbfs_propagate_planes(frontier: jax.Array, seen: jax.Array,
                           src: jax.Array, tgt: jax.Array,
                           block_edges: int = 1024,
                           interpret: bool | None = None,
                           op: str = "or",
                           vector_scatter: bool | None = None):
    """Fused gather/scatter-combine/P3 over packed plane words.

    frontier/seen: uint32[n_rows, nw] — the caller appends a trash row
        (frontier trash = 0, seen trash = all-ones) so invalid edges can
        point at row ``n_rows - 1`` and contribute nothing to the count.
    src/tgt: int32[m] in [0, n_rows), m a multiple of ``block_edges``.
    op: cross-plane merge for the scatter accumulation ("or" | "max").
    interpret: None (default) follows the backend (see
        :func:`repro.kernels.mode.interpret_mode`).
    vector_scatter: None (default) = vectorize the chunk scatter exactly
        when interpreting (see :func:`_chunk_scatter`); pass True/False
        to force either body.

    Returns (new, seen_out, count[1, 1]) where
    new = scatter_combine(frontier[src] -> tgt) & ~seen,
    seen_out = seen | new, count = popcount(new).
    """
    if op not in _COMBINE:
        raise ValueError(f"op must be one of {sorted(_COMBINE)}, got {op!r}")
    interpret = interpret_mode(interpret)
    if vector_scatter is None:
        vector_scatter = interpret
    n_rows, nw = frontier.shape
    m = src.shape[0]
    assert m % block_edges == 0, (m, block_edges)
    plane = pl.BlockSpec((n_rows, nw), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, block_edges=block_edges, op=op,
                          vector_scatter=vector_scatter),
        grid=(m // block_edges,),
        in_specs=[_edge_block(block_edges, lambda i: (i,)),
                  _edge_block(block_edges, lambda i: (i,)),
                  plane, plane],
        out_specs=[plane, plane, _count_block(lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, nw), jnp.uint32),
            jax.ShapeDtypeStruct((n_rows, nw), jnp.uint32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
        name="msbfs_propagate",
    )(src, tgt, frontier, seen)


def _tiled_kernel(chunk_tile_ref, tgt_ref, seen_ref, msg_ref, new_ref,
                  vout_ref, cnt_ref, *, block_edges: int, tile_rows: int,
                  op: str, vector_scatter: bool):
    """One grid step = one edge chunk of one row tile.

    ``chunk_tile_ref`` (SMEM) names the tile each chunk belongs to; it is
    nondecreasing, so a tile's chunks are a contiguous grid run and the
    candidate block (``new_ref``) persists across that run.  The first
    chunk of a run zeroes the accumulator, the last applies P3 for the
    whole tile — between them only the message and target chunks change,
    which is what the Pallas pipeline double-buffers against the resident
    tile.
    """
    combine = _COMBINE[op]
    step = pl.program_id(0)
    nsteps = pl.num_programs(0)
    tile = chunk_tile_ref[step]
    prev_tile = chunk_tile_ref[jnp.maximum(step - 1, 0)]
    next_tile = chunk_tile_ref[jnp.minimum(step + 1, nsteps - 1)]
    is_first = (step == 0) | (tile != prev_tile)
    is_last = (step == nsteps - 1) | (tile != next_tile)

    @pl.when(step == 0)
    def _init_cnt():
        cnt_ref[0, 0] = 0

    @pl.when(is_first)
    def _init_tile():
        new_ref[...] = jnp.zeros_like(new_ref[...])

    row0 = tile * tile_rows
    nw = new_ref.shape[1]

    if vector_scatter:
        new_ref[...] = _chunk_scatter(new_ref[...], tgt_ref[...] - row0,
                                      msg_ref[...].reshape(-1, nw), op)
    else:
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, nw), 1)

        def body(i, carry):
            t = tgt_ref[i] - row0             # tile-local target row
            # the edge's nw message words, scalars from SMEM, as one row
            msg = jnp.zeros((1, nw), jnp.uint32)
            for w in range(nw):
                msg = jnp.where(lanes == w, msg_ref[i * nw + w], msg)
            new_ref[pl.ds(t, 1), :] = combine(new_ref[pl.ds(t, 1), :], msg)
            return carry

        jax.lax.fori_loop(0, block_edges, body, 0)

    @pl.when(is_last)
    def _p3():
        cand = new_ref[...]
        seen = seen_ref[...]
        nf = cand & ~seen
        new_ref[...] = nf
        vout_ref[...] = seen | nf
        cnt_ref[0, 0] = cnt_ref[0, 0] + jnp.sum(
            jax.lax.population_count(nf).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("tile_rows", "block_edges",
                                             "interpret", "op",
                                             "vector_scatter"))
def msbfs_propagate_planes_tiled(seen: jax.Array, msg: jax.Array,
                                 tgt: jax.Array, chunk_tile: jax.Array,
                                 tile_rows: int, block_edges: int = 1024,
                                 interpret: bool | None = None,
                                 op: str = "or",
                                 vector_scatter: bool | None = None):
    """Row-tiled fused scatter-combine/P3 over pre-gathered messages.

    seen: uint32[R, nw] packed plane words, R a multiple of ``tile_rows``
        (pad rows must be all-ones so they never count as discoveries).
    msg: uint32[L * nw] message stream, L = NC * block_edges — edge e's
        nw frontier words at ``msg[e * nw:(e + 1) * nw]``, already gathered
        and bucketed so chunk c holds only edges of tile ``chunk_tile[c]``
        (pad slots carry msg = 0, the combine identity for both "or" and
        "max").  It is flat so that its SMEM chunks carry no lane padding.
    tgt: int32[L] GLOBAL target rows; tgt[e] must lie inside chunk
        e // block_edges's tile (pad slots point at the tile's first row).
    chunk_tile: int32[NC] nondecreasing tile id per chunk, covering every
        tile of ``seen`` at least once (empty tiles get one pad chunk so
        their P3 still runs).
    interpret: None (default) follows the backend.
    vector_scatter: None (default) = vectorize the chunk scatter exactly
        when interpreting (see :func:`_chunk_scatter`).

    Returns (new, seen_out, count[1, 1]) with semantics identical to
    ``msbfs_propagate_planes`` restricted to the streamed edges.
    """
    if op not in _COMBINE:
        raise ValueError(f"op must be one of {sorted(_COMBINE)}, got {op!r}")
    interpret = interpret_mode(interpret)
    if vector_scatter is None:
        vector_scatter = interpret
    n_rows, nw = seen.shape
    assert n_rows % tile_rows == 0, (n_rows, tile_rows)
    num_chunks = chunk_tile.shape[0]
    assert msg.shape == (num_chunks * block_edges * nw,), (
        msg.shape, num_chunks, block_edges, nw)
    tile = pl.BlockSpec((tile_rows, nw), lambda i, ct: (ct[i], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_chunks,),
        in_specs=[
            _edge_block(block_edges, lambda i, ct: (i,)),
            tile,
            _edge_block(block_edges * nw, lambda i, ct: (i,)),
        ],
        out_specs=[tile, tile, _count_block(lambda i, ct: (0, 0))],
    )
    return pl.pallas_call(
        functools.partial(_tiled_kernel, block_edges=block_edges,
                          tile_rows=tile_rows, op=op,
                          vector_scatter=vector_scatter),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, nw), jnp.uint32),
            jax.ShapeDtypeStruct((n_rows, nw), jnp.uint32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
        name="msbfs_propagate_tiled",
    )(chunk_tile, tgt, seen, msg)
