"""Plain reference BFS, and the control that must fail the comparison.

Independent of the program: it imports nothing from ``repro`` and reads
only the benchmark's own CSR.  Up to 64 roots run together, one bit per
root in a ``uint32``/``uint64`` word per vertex; each level ORs the
frontier words over every vertex's neighbour list (``np.bitwise_or.reduceat``
over the CSR), which on a symmetric graph is the set of vertices with a
frontier neighbour.  There is no direction choice, no edge budget and no
early exit: every level reads every arc.

``truncated_push_levels`` is the control.  It is the shortcut a later change
might be tempted by: each level expands only the first ``budget`` arcs of
the frontier's out-lists and drops the rest instead of re-running the level
with a larger budget.  It breaks the guarantee the configurations state
(exact hop levels) and must come out as not correct.
"""
from __future__ import annotations

import numpy as np

INF = 1 << 30          # level of a vertex the root does not reach


def _word_dtype(k: int):
    if not 1 <= k <= 64:
        raise ValueError(f"need 1..64 roots per call, got {k}")
    return np.uint32 if k <= 32 else np.uint64


def _record(levels, new, lvl: int, k: int, dtype):
    idx = np.flatnonzero(new)
    words = new[idx]
    for p in range(k):
        hit = (words >> dtype(p)) & dtype(1)
        levels[p, idx[hit.astype(bool)]] = lvl


def _seed(n: int, roots, dtype):
    k = len(roots)
    frontier = np.zeros(n, dtype)
    np.bitwise_or.at(frontier, np.asarray(roots, np.int64),
                     (dtype(1) << np.arange(k, dtype=dtype)))
    levels = np.full((k, n), INF, np.int32)
    levels[np.arange(k), roots] = 0
    return frontier, levels


def bfs_levels(indptr: np.ndarray, indices: np.ndarray, roots) -> np.ndarray:
    """int32[len(roots), n] hop levels (``INF`` where unreached) on a
    symmetric CSR."""
    n = len(indptr) - 1
    roots = np.asarray(roots, np.int64)
    k = len(roots)
    dtype = _word_dtype(k)
    frontier, levels = _seed(n, roots, dtype)
    seen = frontier.copy()
    nonempty = np.flatnonzero(np.diff(indptr) > 0)
    starts = indptr[nonempty]
    lvl = 0
    while frontier.any():
        cand = np.zeros(n, dtype)
        cand[nonempty] = np.bitwise_or.reduceat(frontier[indices], starts)
        new = cand & ~seen
        seen |= new
        lvl += 1
        _record(levels, new, lvl, k, dtype)
        frontier = new
    return levels


def truncated_push_levels(indptr: np.ndarray, indices: np.ndarray, roots,
                          budget: int = 1 << 20) -> np.ndarray:
    """The control: a push that keeps only the first ``budget`` arcs of
    each level's frontier out-lists (in vertex order) and drops the rest."""
    n = len(indptr) - 1
    roots = np.asarray(roots, np.int64)
    k = len(roots)
    dtype = _word_dtype(k)
    frontier, levels = _seed(n, roots, dtype)
    seen = frontier.copy()
    lvl = 0
    while frontier.any():
        active = np.flatnonzero(frontier)
        deg = indptr[active + 1] - indptr[active]
        keep = np.cumsum(deg) - deg < budget
        active, deg = active[keep], deg[keep]
        offs = (np.repeat(indptr[active] - (np.cumsum(deg) - deg), deg)
                + np.arange(int(deg.sum())))[:budget]
        src = np.repeat(active, deg)[:budget]
        cand = np.zeros(n, dtype)
        np.bitwise_or.at(cand, indices[offs], frontier[src])
        new = cand & ~seen
        seen |= new
        lvl += 1
        _record(levels, new, lvl, k, dtype)
        frontier = new
    return levels


def mismatches(got_rows, want: np.ndarray) -> int:
    """Number of (request, vertex) levels that differ from the reference."""
    return int(sum(int(np.count_nonzero(np.asarray(g, np.int64) != w))
                   for g, w in zip(got_rows, want)))
