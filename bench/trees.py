"""Levels read off parent rows, for readers that count work by level."""
from __future__ import annotations

import numpy as np

from reference import INF


def levels_from_parents(rows) -> np.ndarray:
    """int32[k, n] depth of each vertex in each parent row (``INF`` where
    the parent is -1), by pointer doubling: each round adds the depth
    already summed at a vertex's ancestor and jumps to that ancestor's
    ancestor, until every vertex has reached its root, whose parent is
    itself (ceil(log2 depth) rounds).  On a valid BFS tree the depths are
    the hop levels; a row with a cycle stops after ceil(log2 n) rounds."""
    rows = np.atleast_2d(np.asarray(rows))
    k, n = rows.shape
    ids = np.arange(n, dtype=np.int32)
    out = np.full((k, n), INF, np.int32)
    for par, level in zip(rows, out):
        reached = par >= 0
        anc = np.where(reached, par, ids).astype(np.int32)
        depth = (anc != ids).astype(np.int32)
        for _ in range(max(1, int(n - 1).bit_length())):
            up = anc[anc]
            if np.array_equal(up, anc):
                break
            depth += depth[anc]
            anc = up
        level[reached] = depth[reached]
    return out
