"""Fused P3 bitmap-update kernel (paper §IV-C "Result Writing").

The FPGA's P3 stage writes three structures per accepted vertex: the next
frontier bit, the visited bit, and the level value.  The TPU analogue is an
elementwise fused pass over packed uint32 words held in VMEM:

    new_frontier = candidates & ~visited
    visited'     = visited | new_frontier
    count       += popcount(new_frontier)        (frontier size for the
                                                  Scheduler's mode decision)

Fusing the three ops keeps each word's round trip HBM->VMEM->HBM to a single
pass (the "double pump BRAM: two ops per cycle" analogue), and the popcount
rides along for free instead of a second reduction pass.

Grid: 1-D over row-tiles of a [rows, 128] word array; BlockSpec keeps
(block_rows, 128) word tiles in VMEM (8 KiB at block_rows=16).  The
popcount accumulates in an SMEM block: Mosaic stores no scalars to VMEM.
Interpret mode follows the backend (``repro.kernels.mode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode


def _kernel(cand_ref, vis_ref, nf_ref, vout_ref, cnt_ref):
    cand = cand_ref[...]
    vis = vis_ref[...]
    nf = cand & ~vis
    nf_ref[...] = nf
    vout_ref[...] = vis | nf

    @pl.when(pl.program_id(0) == 0)
    def _init():
        cnt_ref[0, 0] = 0

    cnt_ref[0, 0] += jnp.sum(
        jax.lax.population_count(nf).astype(jnp.int32))


def _kernel_batch(cand_ref, vis_ref, nf_ref, vout_ref, cnt_ref):
    cand = cand_ref[...]
    vis = vis_ref[...]
    nf = cand & ~vis
    nf_ref[...] = nf
    vout_ref[...] = vis | nf

    @pl.when(pl.program_id(1) == 0)
    def _init():
        cnt_ref[0, 0, 0] = 0

    cnt_ref[0, 0, 0] += jnp.sum(
        jax.lax.population_count(nf).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bitmap_update_batch(cand: jax.Array, visited: jax.Array,
                        block_rows: int = 16, interpret: bool | None = None):
    """Fused frontier update over a BATCH of bit-planes.

    cand/visited: uint32[batch, rows, 128] — one plane per 32-source word of
    an MS-BFS batch (or any stack of frontiers sharing a P3 pass).  The grid
    walks (plane, row-tile); each plane's new-bit popcount accumulates into
    its own counter, so the per-source-group discovery counts the Scheduler
    wants ride along for free, exactly like the single-frontier kernel.

    Returns (new_frontier, visited_out, new_counts[batch, 1, 1]).
    """
    b, rows, cols = cand.shape
    assert cols == 128 and rows % block_rows == 0, (b, rows, cols)
    grid = (b, rows // block_rows)
    blk = pl.BlockSpec((1, block_rows, 128), lambda i, j: (i, j, 0))
    return pl.pallas_call(
        _kernel_batch,
        grid=grid,
        in_specs=[blk, blk],
        out_specs=[blk, blk,
                   pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0),
                                memory_space=pltpu.SMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((b, rows, 128), jnp.uint32),
            jax.ShapeDtypeStruct((b, rows, 128), jnp.uint32),
            jax.ShapeDtypeStruct((b, 1, 1), jnp.int32),
        ],
        interpret=interpret_mode(interpret),
    )(cand, visited)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bitmap_update(cand: jax.Array, visited: jax.Array,
                  block_rows: int = 16, interpret: bool | None = None):
    """Fused frontier update on uint32[rows, 128] word arrays.

    Returns (new_frontier, visited_out, new_count).
    """
    rows, cols = cand.shape
    assert cols == 128 and rows % block_rows == 0, (rows, cols)
    grid = (rows // block_rows,)
    blk = pl.BlockSpec((block_rows, 128), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[blk, blk],
        out_specs=[blk, blk, pl.BlockSpec((1, 1), lambda i: (0, 0),
                                          memory_space=pltpu.SMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
            jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret_mode(interpret),
    )(cand, visited)
