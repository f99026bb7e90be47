"""Answers that are BFS parent trees, checked as Graph500 kernel 2 checks
them: a row is ``int[n]``, ``parent[root] == root``, -1 where unreached.

Graph500's validation asks five things of a tree: (1) it has no cycle;
(2) each tree edge joins levels that differ by one; (3) each graph edge
joins levels that differ by at most one, or two vertices outside the tree;
(4) it spans the root's component; (5) each vertex and its parent are
joined by a graph edge.  Graph500 reads the levels off the tree itself.
Here they are the plain reference's exact hop levels (``reference
.bfs_levels``), and the rules are read as three counts, each summed over
the sampled rows, each with limit 0:

- ``parent_not_an_edge`` (rule 5): a reached ``v != root`` whose
  ``(parent[v], v)`` is not an arc of the CSR;
- ``parent_level_wrong`` (rules 1-3): a reached ``v != root`` whose parent
  is not on the exact level ``level[v] - 1``;
- ``tree_span_wrong`` (rule 4): a vertex reached in only one of the tree
  and the reference, a root whose parent is not itself, or an id out of
  range.

Why these three are the five.  Say a tree passes them.  Along a chain of
parents the exact level falls by one per step, so every chain ends, at
the one vertex of level 0, the root: no cycle (1), and a vertex's depth in
the tree is its exact hop distance, so (2) holds and (3) holds as it does
for any BFS distances; (4) and (5) are checked as they stand.  Conversely,
say a tree passes the five with its own depths ``d``.  By (3) ``d`` grows
by at most one along each edge of a shortest path, so ``d(v) <= dist(v)``;
by (5) the tree path to ``v`` is a graph path of ``d(v)`` edges, so
``d(v) >= dist(v)``.  So ``d`` is the exact distance and (2) puts each
parent on the level above.

The control is ``reference.sibling_parents``: the largest-id neighbour no
deeper than the vertex, the shortcut of a push that writes parents after
the level's commit.  The CSR is the benchmark's own (``bench/graph.py``),
whose arcs are sorted by (source, target).
"""
from __future__ import annotations

import numpy as np

from reference import INF, bfs_levels, sibling_parents

NAMES = ("parent_not_an_edge", "parent_level_wrong", "tree_span_wrong")


def reached(row) -> np.ndarray:
    return np.asarray(row) >= 0


def check(indptr, indices, roots, rows) -> dict:
    n = len(indptr) - 1
    bad = dict.fromkeys(NAMES, 0)
    if len(roots):
        levels = bfs_levels(indptr, indices, roots)
        # (source, target) keys of every arc, ascending as the CSR stores them
        arcs = (np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr)) * n
                + indices)
        for root, row, level in zip(roots, rows, levels):
            for name, count in _row_faults(arcs, int(root),
                                           np.asarray(row, np.int64),
                                           level).items():
                bad[name] += count
    return {name: {"value": v, "limit": 0} for name, v in bad.items()}


def _row_faults(arcs, root: int, parent, level) -> dict:
    n = parent.size
    in_range = (parent >= -1) & (parent < n)
    in_tree = in_range & (parent >= 0)
    span = ~in_range | (in_tree != (level < INF))
    span[root] |= parent[root] != root
    inner = in_tree & (level < INF)
    inner[root] = False
    v = np.flatnonzero(inner)
    p = parent[v]
    keys = np.sort(p * n + v)
    at = np.minimum(np.searchsorted(arcs, keys), arcs.size - 1)
    return {"parent_not_an_edge": int(np.count_nonzero(arcs[at] != keys)),
            "parent_level_wrong": int(np.count_nonzero(
                level[p] != level[v] - 1)),
            "tree_span_wrong": int(np.count_nonzero(span))}


def control(indptr, indices, roots) -> np.ndarray:
    return sibling_parents(indptr, indices, roots)
