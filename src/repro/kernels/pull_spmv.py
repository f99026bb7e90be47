"""MXU pull-mode kernel: block-sparse boolean SpMV (beyond-paper, TPU co-design).

Pull-mode BFS is `cand = (A_csc ⊗or.and frontier) ∧ ¬visited` — a boolean
SpMV.  The FPGA streams CSC lists; a TPU has a 128×128 systolic MXU, so for
the *dense hub blocks* of a scale-free graph we store 0/1 adjacency tiles in
bf16 and evaluate the boolean product as a masked matmul:

    out[r] = Σ_c  A_block[r, c] @ f[c]          (f32 accumulate, >0 == OR)

The frontier operand is [block, lanes]: lanes > 1 batches multiple BFS
sources (multi-source BFS), which is what fills the MXU; a single-source
traversal uses lane 0 only.

Blocks arrive sorted by output row; `row_start` flags (scalar-prefetched)
reset the accumulator on the first block of each row, so each output tile is
revisited consecutively across grid steps (sequential-grid accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode


def _kernel(brow_ref, bcol_ref, first_ref, blocks_ref, f_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(first_ref[i] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = blocks_ref[0]                    # [B, B] bf16 0/1 tile
    f = f_ref[0]                         # [B, L] bf16 frontier lanes
    out_ref[0] += jax.lax.dot_general(
        a, f, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_row_blocks", "interpret"))
def pull_spmv_blocks(blocks: jax.Array, block_row: jax.Array,
                     block_col: jax.Array, row_first: jax.Array,
                     frontier: jax.Array, num_row_blocks: int,
                     interpret: bool | None = None) -> jax.Array:
    """Block-sparse boolean SpMV on the MXU.

    blocks:    bf16[nb, B, B]   0/1 adjacency tiles (CSC orientation:
                                rows=children, cols=parents), sorted by row.
    block_row: int32[nb]        output row-block of each tile.
    block_col: int32[nb]        frontier column-block of each tile.
    row_first: int32[nb]        1 on the first tile of each row run.
    frontier:  bf16[ncb, B, L]  frontier lanes per column block.
    returns:   f32[num_row_blocks, B, L]; OR == (out > 0).
    """
    nb, b, _ = blocks.shape
    _, _, lanes = frontier.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, b, b), lambda i, br, bc, fs: (i, 0, 0)),
            pl.BlockSpec((1, b, lanes), lambda i, br, bc, fs: (bc[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, b, lanes),
                               lambda i, br, bc, fs: (br[i], 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_row_blocks, b, lanes),
                                       jnp.float32),
        interpret=interpret_mode(interpret),
    )(block_row, block_col, row_first, blocks, frontier)
