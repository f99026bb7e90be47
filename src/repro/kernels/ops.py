"""Jit'd wrappers that connect the Pallas kernels to the BFS engine.

Interpret mode follows the backend (``repro.kernels.mode``): the same
calls run compiled on a TPU and in the Pallas interpreter on the CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bitmap_update import bitmap_update, bitmap_update_batch
from repro.kernels.csr_gather import gather_pages
from repro.kernels.msbfs_propagate import (msbfs_propagate_planes,
                                           msbfs_propagate_planes_tiled)
from repro.kernels.pull_spmv import pull_spmv_blocks

# VMEM budget for one propagate call's plane working set: the scoped VMEM
# a TPU kernel gets by default (16 MiB on v5e).  Above it
# ``msbfs_propagate`` switches to the row-tiled kernel.
PROPAGATE_VMEM_BYTES = 16 * 1024 * 1024

# Largest streamed edge chunk.  The whole-VMEM kernel streams two int32
# index chunks into SMEM and the tiled kernel a target chunk plus nw
# message words per edge, each double-buffered; SMEM holds 1 MiB.
MAX_BLOCK_EDGES = 4096

LANES = 128


def _row_bytes(nw: int) -> int:
    """VMEM bytes of one ``[rows, nw]`` uint32 plane row: Mosaic tiles the
    minor dimension by 128 lanes, so a row of nw <= 128 words takes 512."""
    return 4 * (-(-nw // LANES) * LANES)


def _plane_footprint_bytes(n_rows: int, nw: int) -> int:
    """Whole-VMEM kernel working set, incl. the trash row: the four plane
    arrays plus the P3 values Mosaic keeps in scoped VMEM, about eight
    lane-padded rows per vertex."""
    return 8 * (n_rows + 1) * _row_bytes(nw)


def _auto_tile_rows(nw: int, vmem_bytes: int) -> int:
    """Tile-size rule: the tiled kernel's P3 holds about six tile-sized
    values in scoped VMEM beside the resident tiles, so budget eight
    lane-padded rows per tile row and round down to the 8-row sublane
    multiple (int32 min tile is (8, 128))."""
    return max((vmem_bytes // (8 * _row_bytes(nw))) // 8 * 8, 8)


def _auto_block_edges(m: int, nw: int) -> int:
    """Edge-chunk length for the streamed index/message blocks.

    The grid runs one step per chunk, so a fixed small chunk at
    graph500-class budgets means tens of thousands of grid steps — pure
    pipeline overhead, and interpret mode runs every step.  The chunk
    therefore grows with m, targeting <= 256 real-edge steps, up to the
    SMEM bound ``MAX_BLOCK_EDGES / nw``.  Always a multiple of the 1024
    floor, so sub-1024 budgets share one compiled shape."""
    cap = max((MAX_BLOCK_EDGES // nw) // 1024 * 1024, 1024)
    need = -(-(-(-m // 256)) // 1024) * 1024
    return int(min(max(need, 1024), cap))


def propagate_plan(n_rows: int, nw: int, tile_rows: int | None = None,
                   vmem_bytes: int | None = None) -> dict:
    """Whole-VMEM vs row-tiled selection for ``msbfs_propagate``.

    ``tile_rows``: None = auto (tile iff the 4-plane footprint exceeds the
    VMEM budget), 0 = force whole-VMEM, > 0 = force tiling at that size.
    Returns dict(tiled, tile_rows, num_tiles, footprint_bytes).
    """
    vmem = PROPAGATE_VMEM_BYTES if vmem_bytes is None else vmem_bytes
    fp = _plane_footprint_bytes(n_rows, nw)
    if tile_rows == 0 or (tile_rows is None and fp <= vmem):
        return dict(tiled=False, tile_rows=0, num_tiles=1,
                    footprint_bytes=fp)
    if tile_rows is None:
        tile_rows = _auto_tile_rows(nw, vmem)
    tile_rows = int(tile_rows)
    if tile_rows < 1:
        raise ValueError(f"tile_rows must be >= 1, got {tile_rows}")
    return dict(tiled=True, tile_rows=tile_rows,
                num_tiles=-(-n_rows // tile_rows), footprint_bytes=fp)


def _bucket_edges_by_tile(msg: jax.Array, tgt: jax.Array, ok: jax.Array,
                          num_tiles: int, tile_rows: int, block_edges: int):
    """Bucket a budgeted edge list by target row tile (jnp, jit-static).

    Builds the streamed inputs of ``msbfs_propagate_planes_tiled``: a
    stable sort groups edges by ``tgt // tile_rows``, each tile's bucket is
    cut into ``block_edges``-sized chunks, and the chunks are laid out
    tile-major so ``chunk_tile`` is nondecreasing (the kernel's
    accumulator-persistence invariant).  Degree-aware budget tiling falls
    out of the counting: chunk capacity is allocated per tile from the
    ACTUAL bucket sizes, so a hub vertex whose in-edges concentrate on one
    tile simply gets more chunks there — the total stays within the static
    ceil(m / C) + T bound (each tile wastes at most one partial chunk, and
    empty tiles get one pad chunk so their P3 still fires).

    msg: uint32[m, nw] pre-gathered frontier words (invalid slots zeroed).
    tgt: int32[m] global target rows; ``ok`` False slots are dropped.
    Returns (stream_msg uint32[L, nw], stream_tgt int32[L],
    chunk_tile int32[NC]) with L = NC * block_edges; pad slots carry
    msg = 0 aimed at their chunk's tile base row (a combine no-op).
    """
    m, nw = msg.shape
    t_, c_ = num_tiles, block_edges
    num_chunks = -(-m // c_) + t_
    l_ = num_chunks * c_
    tile = jnp.where(ok, tgt // tile_rows, t_).astype(jnp.int32)
    order = jnp.argsort(tile)                      # stable in jax
    tile_s = tile[order]
    counts = jnp.bincount(tile, length=t_ + 1).astype(jnp.int32)
    seg_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(m, dtype=jnp.int32) - seg_start[tile_s]
    chunks_per_tile = jnp.maximum(-(-counts[:t_] // c_), 1)
    chunk_off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(chunks_per_tile)[:-1]])
    pos = jnp.where(tile_s < t_,
                    chunk_off[jnp.minimum(tile_s, t_ - 1)] * c_ + rank,
                    l_).astype(jnp.int32)
    # tile id per chunk; trailing unused chunks ride the last tile so the
    # sequence stays nondecreasing and the last tile's P3 stays last
    chunk_tile = jnp.searchsorted(
        jnp.cumsum(chunks_per_tile), jnp.arange(num_chunks, dtype=jnp.int32),
        side="right").astype(jnp.int32)
    chunk_tile = jnp.minimum(chunk_tile, t_ - 1)
    stream_msg = jnp.zeros((l_, nw), jnp.uint32).at[pos].set(
        msg[order], mode="drop")
    default_tgt = chunk_tile[jnp.arange(l_) // c_] * tile_rows
    stream_tgt = default_tgt.at[pos].set(
        jnp.where(ok, tgt, 0).astype(jnp.int32)[order], mode="drop")
    return stream_msg, stream_tgt, chunk_tile


def _propagate_tiled(seen_w: jax.Array, msg: jax.Array, tgt: jax.Array,
                     ok: jax.Array, tile_rows: int, block_edges: int,
                     interpret: bool | None, op: str):
    """Shared tiled-path tail: pad rows to a tile multiple, bucket, run."""
    n, nw = seen_w.shape
    t_ = -(-n // tile_rows)
    r_ = t_ * tile_rows
    if r_ > n:
        # pad rows: seen all-ones, so stray writes never count as
        # discoveries (the tiled path's analogue of the trash row)
        seen_w = jnp.concatenate(
            [seen_w, jnp.full((r_ - n, nw), 0xFFFFFFFF, jnp.uint32)])
    sm, st, ct = _bucket_edges_by_tile(msg, tgt, ok, t_, tile_rows,
                                       block_edges)
    new, vout, cnt = msbfs_propagate_planes_tiled(
        seen_w, sm.reshape(-1), st, ct, tile_rows=tile_rows,
        block_edges=block_edges, interpret=interpret, op=op)
    return new[:n], vout[:n], cnt[0, 0]


def msbfs_propagate(frontier_w: jax.Array, seen_w: jax.Array,
                    src: jax.Array, tgt: jax.Array, valid: jax.Array,
                    block_edges: int | None = None,
                    interpret: bool | None = None,
                    op: str = "or", tile_rows: int | None = None):
    """Fused P2->P3 vertex-program propagate: gather ``frontier_w[src]``
    words and scatter-combine them into the candidate planes at ``tgt``
    (``op``: "or" for bit-planes, "max" for payload planes), then commit
    ``new = cand & ~seen`` / ``seen |= new`` in the same kernel pass.

    frontier_w/seen_w: uint32[n_pad, nw] packed plane words.
    src/tgt: int32[m] edge endpoints; slots with ``valid`` False (or any
    out-of-range index) are dropped.  ``tile_rows`` picks the kernel
    variant (see :func:`propagate_plan`): by default graphs whose 4-plane
    working set exceeds ``PROPAGATE_VMEM_BYTES`` run the row-tiled kernel.
    ``block_edges`` (None = auto, :func:`_auto_block_edges`) is the
    streamed edge-chunk length — one grid step each.
    Returns (new, seen_out, new_count).
    """
    n, nw = frontier_w.shape
    m = src.shape[0]
    if m == 0:
        new = jnp.zeros_like(frontier_w)
        return new, seen_w, jnp.int32(0)
    if block_edges is None:
        block_edges = _auto_block_edges(m, nw)
    ok = valid & (src >= 0) & (src < n) & (tgt >= 0) & (tgt < n)
    plan = propagate_plan(n, nw, tile_rows)
    if plan["tiled"]:
        # pre-gather the messages (an XLA HBM gather): the tiled kernel
        # streams them per tile and never holds the frontier in VMEM
        msg = jnp.where(ok[:, None], frontier_w[jnp.maximum(src, 0)],
                        jnp.uint32(0))
        return _propagate_tiled(seen_w, msg, tgt, ok, plan["tile_rows"],
                                block_edges, interpret, op)
    # trash row n: zero frontier mask (contributes nothing), all-ones seen
    # (so the trash candidates never count as discoveries)
    f1 = jnp.concatenate([frontier_w, jnp.zeros((1, nw), jnp.uint32)])
    s1 = jnp.concatenate(
        [seen_w, jnp.full((1, nw), 0xFFFFFFFF, jnp.uint32)])
    sidx = jnp.where(ok, src, n).astype(jnp.int32)
    tidx = jnp.where(ok, tgt, n).astype(jnp.int32)
    # always pad m up to whole ``block_edges`` chunks: baking a raw small
    # m into the static block size compiled a fresh pallas_call per
    # distinct tiny budget
    pad = (-m) % block_edges
    if pad:
        sidx = jnp.pad(sidx, (0, pad), constant_values=n)
        tidx = jnp.pad(tidx, (0, pad), constant_values=n)
    new, vout, cnt = msbfs_propagate_planes(f1, s1, sidx, tidx,
                                            block_edges=block_edges,
                                            interpret=interpret, op=op)
    return new[:-1], vout[:-1], cnt[0, 0]


def msbfs_propagate_msgs(seen_w: jax.Array, msg: jax.Array, tgt: jax.Array,
                         valid: jax.Array, tile_rows: int | None = None,
                         block_edges: int | None = None,
                         interpret: bool | None = None, op: str = "or"):
    """Msgs-form fused propagate: like :func:`msbfs_propagate` but with the
    frontier gather already done — ``msg[e]`` is the packed plane word edge
    ``e`` carries into row ``tgt[e]``.  This is the natural entry when the
    gather happens under a different sharding than the scatter (the
    distributed pull path gathers from the all-gathered global frontier
    but scatters into shard-local rows).  Always runs the row-tiled
    kernel; ``tile_rows`` defaults to the auto rule of
    :func:`propagate_plan`.  Returns (new, seen_out, new_count).
    """
    n, nw = seen_w.shape
    m = tgt.shape[0]
    if m == 0:
        new = jnp.zeros_like(seen_w)
        return new, seen_w, jnp.int32(0)
    if block_edges is None:
        block_edges = _auto_block_edges(m, nw)
    if tile_rows is None:
        tile_rows = min(_auto_tile_rows(nw, PROPAGATE_VMEM_BYTES), n)
    tile_rows = int(tile_rows)
    if tile_rows < 1:
        raise ValueError(f"tile_rows must be >= 1, got {tile_rows}")
    ok = valid & (tgt >= 0) & (tgt < n)
    msg = jnp.where(ok[:, None], msg, jnp.uint32(0))
    return _propagate_tiled(seen_w, msg, tgt, ok, tile_rows, block_edges,
                            interpret, op)


def _pad_rows_to_block(rows: int, cap: int = 16) -> tuple[int, int]:
    """Grid plan for the row-blocked P3 kernels: ``block_rows = min(rows,
    cap)`` with ``rows`` padded up to a whole multiple.  (The old plan
    hunted for an exact divisor <= cap, which degrades to 1-row blocks —
    a ``rows``-step grid — whenever the row count is prime.)  The pad rows
    are zeros: cand 0 & ~visited contributes no new bits and no count."""
    block = min(rows, cap)
    return -(-rows // block) * block, block


def fused_frontier_update(cand_words: jax.Array, visited_words: jax.Array):
    """P3 update on flat uint32[w] words; returns (new, visited, count)."""
    w = cand_words.shape[0]
    rows = max((w + 127) // 128, 1)
    rows_pad, block_rows = _pad_rows_to_block(rows)
    pad = rows_pad * 128 - w
    c2 = jnp.pad(cand_words, (0, pad)).reshape(rows_pad, 128)
    v2 = jnp.pad(visited_words, (0, pad)).reshape(rows_pad, 128)
    nf, vo, cnt = bitmap_update(c2, v2, block_rows=block_rows)
    return (nf.reshape(-1)[:w], vo.reshape(-1)[:w], cnt[0, 0])


def fused_frontier_update_batch(cand_words: jax.Array,
                                visited_words: jax.Array):
    """P3 update on a stack of planes: uint32[g, w] -> (new, visited,
    counts[g]).  One fused pass per plane, per-plane popcounts riding
    along (the MS-BFS per-source-word discovery counters)."""
    g, w = cand_words.shape
    rows = max((w + 127) // 128, 1)
    rows_pad, block_rows = _pad_rows_to_block(rows)
    pad = rows_pad * 128 - w
    c2 = jnp.pad(cand_words, ((0, 0), (0, pad))).reshape(g, rows_pad, 128)
    v2 = jnp.pad(visited_words, ((0, 0), (0, pad))).reshape(g, rows_pad, 128)
    nf, vo, cnt = bitmap_update_batch(c2, v2, block_rows=block_rows)
    return (nf.reshape(g, -1)[:, :w], vo.reshape(g, -1)[:, :w],
            cnt.reshape(g))


def build_page_table(starts: np.ndarray, degrees: np.ndarray, page: int,
                     budget_pages: int):
    """Host-side helper: (start, degree) pairs -> page table + masks.

    Returns (page_ids int32[budget_pages], item_vertex int32[budget_pages],
    first_offset int32[budget_pages]) where page_ids[i] is the page to fetch
    for work item i and first_offset marks the in-page start of the list.
    """
    page_ids, owner, offs = [], [], []
    for v, (s, d) in enumerate(zip(starts, degrees)):
        if d <= 0:
            continue
        p0, p1 = s // page, (s + d - 1) // page
        for p in range(p0, p1 + 1):
            page_ids.append(p)
            owner.append(v)
            offs.append(s - p * page if p == p0 else 0)
    k = len(page_ids)
    if k > budget_pages:
        raise OverflowError(f"page table {k} > budget {budget_pages}")
    pad = budget_pages - k
    return (np.asarray(page_ids + [0] * pad, np.int32),
            np.asarray(owner + [-1] * pad, np.int32),
            np.asarray(offs + [0] * pad, np.int32))


def read_neighbor_pages(edges: jax.Array, page_ids: jax.Array, page: int):
    """HBM-reader op: fetch the pages listed in ``page_ids``.

    edges is the flat int32 edge array (padded to a page multiple).
    """
    paged = edges.reshape(-1, page)
    return gather_pages(paged, page_ids)


def pull_spmv(blocks, block_row, block_col, frontier, num_row_blocks: int):
    """Boolean block SpMV; returns packed OR result as bool[rb, B, L]."""
    row_first = jnp.concatenate(
        [jnp.ones((1,), jnp.int32),
         (block_row[1:] != block_row[:-1]).astype(jnp.int32)])
    acc = pull_spmv_blocks(blocks, block_row, block_col, row_first, frontier,
                           num_row_blocks=num_row_blocks)
    return acc > 0
