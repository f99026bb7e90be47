"""Answers that are BFS hop levels: a row is ``int[n]``, 0 at the root and
``reference.INF`` where the root does not reach.

The check compares every vertex of every sampled row with the plain
reference's exact levels (``reference.bfs_levels``): the configurations
state exact hop levels, so the limit is 0.  The control is
``reference.truncated_push_levels``, a push that drops the arcs past a
fixed budget instead of re-running the level.
"""
from __future__ import annotations

import numpy as np

from reference import INF, bfs_levels, mismatches, truncated_push_levels


def reached(row) -> np.ndarray:
    return np.asarray(row) < INF


def check(indptr, indices, roots, rows) -> dict:
    n = len(indptr) - 1
    want = (bfs_levels(indptr, indices, roots) if len(roots)
            else np.zeros((0, n), np.int32))
    return {"wrong_levels": {"value": mismatches(rows, want), "limit": 0}}


def control(indptr, indices, roots) -> np.ndarray:
    return truncated_push_levels(indptr, indices, roots)
