"""Single-device BFS engine implementing the paper's Algorithm 2.

Three bitmaps (current frontier / next frontier / visited) + a level array.
Two execution paths:

* ``bfs_reference`` — fully-jit `lax.while_loop`, edge-parallel (dense) steps.
  This is the correctness oracle-adjacent path used by tests and by the
  distributed engine's per-shard step.
* ``BFSRunner`` — work-efficient gather path mirroring the hardware pipeline
  P1 (workload prep: frontier compaction), P2 (neighbor checking: CSR/CSC
  gather + bitmap tests), P3 (result writing: fused bitmap update).  It
  counts *inspected edges* per mode, which is what the paper's Fig. 8/10
  comparisons measure, and drives GTEPS benchmarks.

The batched multi-source engines (MS-BFS, CC, SSSP) live in
``repro.core.vertex_program``; this module provides the shared primitives
they build on (``LocalGraph``, ``compact_indices``, ``expand_edges``, the
``SV_*`` statvec layout, ``validate_roots``) plus the single-source
pipeline.  Both drivers share the one-sync-per-level protocol: every step
returns a stacked int32 stats vector fused into the step itself, so each
level pays exactly ONE blocking device->host transfer.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap
from repro.core.scheduler import PUSH, SchedulerConfig, choose_mode_host
from repro.graph.csr import CSRGraph, edge_sources

INF = jnp.int32(2 ** 30)

# Layout of the per-level fused stats vector (int32[7]) every step returns:
# next-frontier stats for the Scheduler, this step's edge total + overflow
# flag, and the new-discovery popcount — ONE device->host transfer per level.
SV_NF, SV_MF, SV_MU, SV_NU, SV_TOTAL, SV_OVERFLOW, SV_COUNT = range(7)


@partial(jax.tree_util.register_dataclass,
         data_fields=("out_indptr", "out_indices", "in_indptr", "in_indices",
                      "out_src", "in_child", "out_deg", "in_deg",
                      "in_seg_end"),
         meta_fields=("n", "n_pad"))
@dataclasses.dataclass(frozen=True)
class LocalGraph:
    """Device-resident graph arrays (vertex space padded to words).

    All index arrays are int32 (graphs up to 2**31 edges; enable
    jax_enable_x64 for larger — host-side construction is already int64).
    Degrees are precomputed once at build time (they feed the per-level
    scheduler stats; re-deriving them with ``jnp.diff`` every level was
    pure waste), as are the CSC segment descriptors the scan-based pull
    propagate uses (``in_child`` as the segment ids, ``in_seg_end``).
    """

    n: int
    n_pad: int
    out_indptr: jax.Array   # int32[n_pad+1]
    out_indices: jax.Array  # int32[E]
    in_indptr: jax.Array
    in_indices: jax.Array
    out_src: jax.Array      # int32[E] edge-parallel CSR sources
    in_child: jax.Array     # int32[E] edge-parallel CSC rows (children)
    out_deg: jax.Array      # int32[n_pad] stored out-degrees
    in_deg: jax.Array       # int32[n_pad] stored in-degrees
    in_seg_end: jax.Array    # int32[n_pad] last in-edge per child (-1: none)


def _sorted_in_lists(csc: CSRGraph) -> CSRGraph:
    """``csc`` with every in-list in ascending source order.

    The dense pull's parent payload takes the first frontier arc of each
    in-list as the parent, which is the smallest-id one only when the
    lists are sorted (``csr_from_edges`` and ``transpose_csr`` build them
    so; a hand-made CSC may not be).  One pass to check, a sort only when
    some list descends."""
    idx = np.asarray(csc.indices)
    down = np.flatnonzero(idx[1:] < idx[:-1]) + 1
    starts = np.zeros(idx.size + 1, bool)
    starts[np.asarray(csc.indptr)] = True
    if starts[down].all():
        return csc
    rows = np.repeat(np.arange(csc.num_vertices), np.diff(csc.indptr))
    return CSRGraph(csc.num_vertices, csc.indptr,
                    idx[np.lexsort((idx, rows))])


def build_local_graph(csr: CSRGraph, csc: CSRGraph) -> LocalGraph:
    n = csr.num_vertices
    if csc is not csr:
        csc = _sorted_in_lists(csc)
    else:
        csr = csc = _sorted_in_lists(csr)
    n_pad = bitmap.num_words(n) * bitmap.WORD_BITS

    def pad_ptr(indptr):
        return np.concatenate(
            [indptr, np.full(n_pad - n, indptr[-1], dtype=indptr.dtype)])

    out_ptr = pad_ptr(csr.indptr)
    in_ptr = pad_ptr(csc.indptr)
    in_deg = np.diff(in_ptr)
    in_end = np.where(in_deg > 0, in_ptr[1:] - 1, -1)
    out_indptr = jnp.asarray(out_ptr.astype(np.int32))
    out_indices = jnp.asarray(csr.indices)
    out_src = jnp.asarray(edge_sources(csr))
    out_deg = jnp.asarray(np.diff(out_ptr).astype(np.int32))
    if csc is csr:
        # a symmetric graph is its own transpose: one copy on the device
        in_indptr, in_indices, in_child, in_deg_dev = (
            out_indptr, out_indices, out_src, out_deg)
    else:
        in_indptr = jnp.asarray(in_ptr.astype(np.int32))
        in_indices = jnp.asarray(csc.indices)
        in_child = jnp.asarray(edge_sources(csc))
        in_deg_dev = jnp.asarray(in_deg.astype(np.int32))

    return LocalGraph(
        n=n, n_pad=n_pad,
        out_indptr=out_indptr, out_indices=out_indices,
        in_indptr=in_indptr, in_indices=in_indices,
        out_src=out_src, in_child=in_child,
        out_deg=out_deg, in_deg=in_deg_dev,
        in_seg_end=jnp.asarray(in_end.astype(np.int32)),
    )


# ---------------------------------------------------------------------------
# Dense (edge-parallel) steps: O(E) work, trivially correct, fully jit.
# ---------------------------------------------------------------------------

def _dense_step(g: LocalGraph, frontier_w, visited_w):
    """One level expansion; returns candidate bitmap words (global)."""
    fmask = bitmap.unpack(frontier_w, g.n_pad)
    msg = fmask[g.out_src]                       # active source per CSR edge
    cand = jnp.zeros((g.n_pad,), jnp.bool_).at[g.out_indices].max(msg)
    return bitmap.pack(cand)


def bfs_reference(g: LocalGraph, root: int, max_iters: int | None = None):
    """Fully-jit Algorithm 2 loop (dense steps).  Returns level int32[n]."""
    max_iters = max_iters or g.n_pad

    def cond(state):
        frontier, visited, level, lvl = state
        return (bitmap.popcount(frontier) > 0) & (lvl < max_iters)

    def body(state):
        frontier, visited, level, lvl = state
        cand = _dense_step(g, frontier, visited)
        new = cand & ~visited                     # P3: next |= cand & ~visited
        visited = visited | new
        new_mask = bitmap.unpack(new, g.n_pad)
        level = jnp.where(new_mask, lvl + 1, level)
        return new, visited, level, lvl + 1

    frontier0 = bitmap.from_indices_dense(jnp.array([root]), g.n_pad)
    visited0 = frontier0
    level0 = jnp.full((g.n_pad,), INF, jnp.int32).at[root].set(0)
    frontier, visited, level, lvl = jax.lax.while_loop(
        cond, body, (frontier0, visited0, level0, jnp.int32(0)))
    return level[: g.n]


# ---------------------------------------------------------------------------
# Work-efficient gather pipeline (P1 -> P2 -> P3), mirroring the PE stages.
# ---------------------------------------------------------------------------

def compact_indices(mask: jax.Array, cap: int) -> tuple[jax.Array, jax.Array]:
    """P1 workload prep: indices of set bits, padded with -1 to ``cap``."""
    idx = jnp.nonzero(mask, size=cap, fill_value=-1)[0]
    return idx.astype(jnp.int32), jnp.sum(mask, dtype=jnp.int32)


OWNER_MERGE_RATIO = 16


def owner_path(n: int, budget: int) -> str:
    """How :func:`expand_edges` finds the owners of ``budget`` slots over
    ``n`` active entries, from those static shapes alone.

    ``"search"`` is ``jnp.searchsorted``: a loop of ceil(log2(n + 1))
    rounds, each a gather of ``budget`` entries of ``cum``.  ``"merge"``
    is one scatter-add of the ``n`` cumulative degrees into ``budget + 1``
    bins and a cumsum: cheaper once ``budget * OWNER_MERGE_RATIO >= n``
    (the crossover measured on a TPU v5e at n = 2^22, PERF.md §5)."""
    return "merge" if budget * OWNER_MERGE_RATIO >= n else "search"


def search_owners(cum: jax.Array, budget: int) -> jax.Array:
    """``owner[e] = #{k : cum[k] <= e}`` for each slot ``e < budget``,
    by binary search (``cum`` non-decreasing)."""
    e = jnp.arange(budget, dtype=jnp.int32)
    return jnp.searchsorted(cum, e, side="right").astype(jnp.int32)


def merge_owners(cum: jax.Array, budget: int) -> jax.Array:
    """:func:`search_owners`, bit for bit, by one merge: slots and ``cum``
    are both sorted, so counting the ``cum`` entries in each slot's bin
    (those past the budget share the last bin) and summing the counts
    gives every slot its owner."""
    hist = jnp.zeros(budget + 1, jnp.int32).at[jnp.minimum(cum, budget)].add(
        1, indices_are_sorted=True, mode="promise_in_bounds")
    return jnp.cumsum(hist)[:budget]


def expand_edges(active: jax.Array, indptr: jax.Array, indices: jax.Array,
                 budget: int):
    """P2 neighbor gather: flatten the neighbor lists of ``active`` vertices.

    Returns (sources, neighbors, valid, total_edges).  ``total_edges`` may
    exceed ``budget`` — the caller must treat that as overflow and retry with
    a bigger budget (the HBM-reader queue depth analogue).  Each slot's
    owner comes by the path :func:`owner_path` picks for the shapes.
    """
    a = jnp.maximum(active, 0)
    deg = (indptr[a + 1] - indptr[a]) * (active >= 0)
    cum = jnp.cumsum(deg)
    total = cum[-1]
    e = jnp.arange(budget, dtype=jnp.int32)
    owners = (merge_owners if owner_path(active.shape[0], budget) == "merge"
              else search_owners)
    owner = owners(cum, budget)
    owner_c = jnp.minimum(owner, active.shape[0] - 1)
    start = cum[owner_c] - deg[owner_c]
    src = active[owner_c]
    eidx = indptr[jnp.maximum(src, 0)] + (e - start)
    valid = e < total
    nbr = indices[jnp.where(valid, eidx, 0)]
    return (jnp.where(valid, src, -1),
            jnp.where(valid, nbr, -1).astype(jnp.int32), valid, total)


def _p3_update(cand_w, visited_w, use_pallas: bool):
    """P3 result writing: fused Pallas kernel or plain jnp (same semantics)."""
    if use_pallas:
        from repro.kernels import ops as kops
        new, vis2, _ = kops.fused_frontier_update(cand_w, visited_w)
        return new, vis2
    new = cand_w & ~visited_w
    return new, visited_w | new


def _statvec(g: LocalGraph, new_w, visited_w, total, overflow):
    """Fused per-level stats (single-source): one stacked int32[7]."""
    fmask = bitmap.unpack(new_w, g.n_pad)
    umask = ~bitmap.unpack(visited_w, g.n_pad)
    return jnp.stack([
        jnp.sum(fmask, dtype=jnp.int32),
        jnp.sum(jnp.where(fmask, g.out_deg, 0), dtype=jnp.int32),
        jnp.sum(jnp.where(umask, g.in_deg, 0), dtype=jnp.int32),
        jnp.sum(umask, dtype=jnp.int32),
        jnp.asarray(total, jnp.int32),
        jnp.asarray(overflow, jnp.int32),
        bitmap.popcount(new_w),
    ])


@jax.jit
def _sbfs_init(g: LocalGraph, roots):
    frontier = bitmap.from_indices_dense(roots, g.n_pad)
    level = jnp.full((g.n_pad,), INF, jnp.int32).at[roots[0]].set(0)
    return (frontier, frontier, level,
            _statvec(g, frontier, frontier, 0, 0))


@partial(jax.jit, static_argnames=("budget", "use_pallas"))
def push_step(g: LocalGraph, frontier_w, visited_w, level, lvl, budget: int,
              use_pallas: bool = False):
    """Push iteration: expand out-lists of frontier, filter by visited.

    Level update and next-level stats are folded in; returns
    (new, visited, level, statvec) — the driver fetches only ``statvec``.
    """
    fmask = bitmap.unpack(frontier_w, g.n_pad)
    active, _ = compact_indices(fmask, g.n_pad)
    _, nbr, valid, total = expand_edges(active, g.out_indptr, g.out_indices,
                                        budget)
    unvisited = ~bitmap.test_bits(visited_w, jnp.maximum(nbr, 0)) & valid
    cand = bitmap.from_indices_dense(jnp.where(unvisited, nbr, -1), g.n_pad)
    new, vis2 = _p3_update(cand, visited_w, use_pallas)
    level2 = jnp.where(bitmap.unpack(new, g.n_pad), lvl + 1, level)
    return new, vis2, level2, _statvec(g, new, vis2, total, total > budget)


@partial(jax.jit, static_argnames=("budget", "use_pallas"))
def pull_step(g: LocalGraph, frontier_w, visited_w, level, lvl, budget: int,
              use_pallas: bool = False):
    """Pull iteration: expand in-lists of unvisited, test frontier bit."""
    umask = ~bitmap.unpack(visited_w, g.n_pad)
    unvisited, _ = compact_indices(umask, g.n_pad)
    child, parent, valid, total = expand_edges(
        unvisited, g.in_indptr, g.in_indices, budget)
    hit = bitmap.test_bits(frontier_w, jnp.maximum(parent, 0)) & valid
    cand = bitmap.from_indices_dense(jnp.where(hit, child, -1), g.n_pad)
    new, vis2 = _p3_update(cand, visited_w, use_pallas)
    level2 = jnp.where(bitmap.unpack(new, g.n_pad), lvl + 1, level)
    return new, vis2, level2, _statvec(g, new, vis2, total, total > budget)


@dataclasses.dataclass
class BFSResult:
    level: np.ndarray
    iterations: int
    edges_inspected: int
    push_iters: int
    pull_iters: int
    traversed_edges: int
    seconds: float
    host_transfers: int = 0     # blocking device->host fetches during run

    @property
    def gteps(self) -> float:
        return self.traversed_edges / max(self.seconds, 1e-12) / 1e9


class BFSRunner:
    """Python-driven hybrid BFS with budgeted gather steps (bench engine).

    One-sync-per-level driver: every step returns its successor's stats as
    a stacked int32 vector, so the loop performs exactly one blocking
    device->host transfer per level (plus one for the initial frontier and
    one final level-array readback).
    """

    def __init__(self, g: LocalGraph, sched: SchedulerConfig | None = None,
                 init_budget: int = 1 << 15, use_pallas: bool = False):
        self.g = g
        self.sched = sched or SchedulerConfig()
        self.init_budget = init_budget
        self.use_pallas = use_pallas
        self._transfers = 0
        # fetched once here so the GTEPS accounting after each run is not
        # an extra (uncounted) device->host transfer
        self._out_deg_np = np.asarray(g.out_deg)[: g.n]

    @property
    def num_vertices(self) -> int:
        return int(self.g.n)

    @property
    def out_deg(self) -> np.ndarray:
        """Out-degrees [n] (the engine protocol's TEPS numerator input)."""
        return self._out_deg_np

    def _fetch(self, arr) -> np.ndarray:
        self._transfers += 1
        return np.asarray(arr)

    def run(self, root: int) -> BFSResult:
        g = self.g
        self._transfers = 0
        t0 = time.perf_counter()
        frontier, visited, level, statvec = _sbfs_init(
            g, jnp.asarray([root], jnp.int32))
        sv = self._fetch(statvec)
        mode = PUSH
        lvl = 0
        inspected = 0
        push_iters = pull_iters = 0
        # no point budgeting past the whole edge array (keeps the budgeted
        # kernels small on tiny graphs); the overflow loop still deepens
        budget = min(self.init_budget,
                     max(g.out_indices.shape[0], g.in_indices.shape[0]) + 1)
        while int(sv[SV_NF]) > 0:
            mode = choose_mode_host(self.sched, mode, int(sv[SV_NF]),
                                    int(sv[SV_MF]), int(sv[SV_MU]), g.n,
                                    int(sv[SV_NU]))
            step = push_step if mode == PUSH else pull_step
            need = int(sv[SV_MF]) if mode == PUSH else int(sv[SV_MU])
            cap = (g.out_indices if mode == PUSH else g.in_indices).shape[0]
            while budget < min(need, cap + 1):
                budget *= 2
            # retry from the PRE-step visited: an overflowed (truncated)
            # step may have committed a partial discovery set
            state0 = (frontier, visited, level)
            frontier, visited, level, statvec = step(
                g, *state0, np.int32(lvl), budget, self.use_pallas)
            sv = self._fetch(statvec)
            while bool(sv[SV_OVERFLOW]):   # HBM-reader overflow: deepen
                budget *= 2
                frontier, visited, level, statvec = step(
                    g, *state0, np.int32(lvl), budget, self.use_pallas)
                sv = self._fetch(statvec)
            lvl += 1
            inspected += int(sv[SV_TOTAL])
            if mode == PUSH:
                push_iters += 1
            else:
                pull_iters += 1
        level.block_until_ready()
        dt = time.perf_counter() - t0
        level_np = self._fetch(level[: g.n])
        # GTEPS metric per paper §VI-A: sum of outgoing neighbor-list lengths
        # of all visited vertices; each edge counted once.
        traversed = count_traversed_edges(self._out_deg_np, level_np)
        return BFSResult(level=level_np, iterations=lvl,
                         edges_inspected=inspected, push_iters=push_iters,
                         pull_iters=pull_iters, traversed_edges=traversed,
                         seconds=dt, host_transfers=self._transfers)


# ---------------------------------------------------------------------------
# Batched multi-source traversal (MS-BFS and friends) lives in
# ``repro.core.vertex_program``: the packed plane exchange, the hybrid
# scheduler loop and the one-sync-per-level statvec protocol were factored
# into a generic vertex-program engine there (BFS / CC / SSSP
# instantiations).  This module keeps the single-source pipeline plus the
# shared primitives the engine builds on (LocalGraph, compact_indices,
# expand_edges, the statvec layout, validate_roots).
# ---------------------------------------------------------------------------

@runtime_checkable
class BFSEngine(Protocol):
    """Minimal contract the serving layers rely on.

    Any batched vertex-program query engine exposes the number of vertices
    of its resident graph, its out-degree array (the per-wave TEPS
    numerator — serving layers no longer sniff ``.g.out_deg``), and
    answers a batch of root queries with a value-rows matrix; per-run
    counters land in ``last_stats``.  ``VertexProgramRunner`` (and its
    BFS/CC/SSSP subclasses) and ``DistributedBFS`` all satisfy this —
    ``launch.dynbatch`` / ``launch.serve`` program against it instead of
    duck-typing on ``.g`` / ``.pg``.
    """

    @property
    def num_vertices(self) -> int: ...

    @property
    def out_deg(self) -> "np.ndarray | None": ...

    def run_batch(self, roots) -> np.ndarray: ...


def validate_roots(roots: np.ndarray, num_vertices: int) -> np.ndarray:
    """Reject malformed MS-BFS root batches with a ``ValueError``.

    A negative or >= |V| root would otherwise scatter silently out of
    bounds (JAX clips/drops out-of-range indices), yielding a wrong answer
    instead of an error.  Duplicate roots ARE allowed — each occupies its
    own bit-plane slot and resolves independently.
    """
    roots = np.asarray(roots)
    if roots.ndim != 1 or roots.size == 0:
        raise ValueError(
            f"roots must be a non-empty 1-D array, got shape {roots.shape}")
    if not np.issubdtype(roots.dtype, np.integer):
        # a float/bool root would pass the range check and then be
        # silently truncated by the engine's integer cast
        raise ValueError(f"roots must be integers, got dtype {roots.dtype}")
    if ((roots < 0) | (roots >= num_vertices)).any():
        bad = roots[(roots < 0) | (roots >= num_vertices)]
        raise ValueError(
            f"roots out of range [0, {num_vertices}): {bad.tolist()[:8]}")
    return roots


def engine_num_vertices(engine) -> int | None:
    """|V| of the graph a BFS engine serves, or None.

    Deprecated shim: engines now expose ``num_vertices`` directly (the
    :class:`BFSEngine` protocol); this forwards to it, keeping the old
    ``.g``/``.pg`` duck-typing as a fallback for wrapper engines that
    predate the protocol.
    """
    n = getattr(engine, "num_vertices", None)
    if n is not None:
        return int(n)
    g = getattr(engine, "g", None)
    if g is not None:
        return int(g.n)
    pg = getattr(engine, "pg", None)
    if pg is not None:
        return int(pg.num_vertices)
    return None


def count_traversed_edges(out_deg: np.ndarray, levels: np.ndarray) -> int:
    """Paper §VI-A GTEPS numerator: out-degrees of reached vertices, summed
    over every source row of ``levels`` ([n] or [B, n], level or parent
    rows) — one masked matvec instead of a python loop over rows."""
    levels = np.atleast_2d(np.asarray(levels))
    # level rows reach where < INF, parent rows where >= 0
    reached = (levels >= 0) & (levels < int(INF))   # [B, n]
    return int((reached @ np.asarray(out_deg, dtype=np.int64)).sum())


def bfs_oracle(csr: CSRGraph, root: int) -> np.ndarray:
    """Level-synchronous numpy BFS over the CSR arrays — the correctness
    oracle.  Returns int64[n] hop levels from ``root`` (INF = unreached);
    it shares no code with the engines."""
    n = csr.num_vertices
    indptr = np.asarray(csr.indptr, np.int64)
    unreached = int(INF)
    level = np.full(n, unreached, dtype=np.int64)
    level[root] = 0
    frontier = np.asarray([root], np.int64)
    lvl = 0
    while frontier.size:
        starts = indptr[frontier]
        degs = indptr[frontier + 1] - starts
        total = int(degs.sum())
        if total == 0:
            break
        # edge offsets of every frontier vertex's out-list, concatenated
        run_start = np.cumsum(degs) - degs
        offs = np.repeat(starts - run_start, degs) + np.arange(total)
        hit = np.zeros(n, dtype=bool)
        hit[csr.indices[offs]] = True
        frontier = np.flatnonzero(hit & (level == unreached))
        lvl += 1
        level[frontier] = lvl
    return level


def bfs_parent_oracle(csr: CSRGraph, root: int) -> np.ndarray:
    """Plain-Python BFS parent tree — the oracle of the parent program.
    Returns int64[n]: ``root`` at the root, -1 where unreached, else the
    smallest-id in-neighbour one level closer to the root.  A deque BFS
    over the CSR; it shares no code with the engines or ``bfs_oracle``."""
    indptr, indices = csr.indptr.tolist(), csr.indices.tolist()
    depth = [-1] * csr.num_vertices
    parent = [-1] * csr.num_vertices
    depth[root], parent[root] = 0, root
    queue = deque([root])
    while queue:
        u = queue.popleft()
        for v in indices[indptr[u]:indptr[u + 1]]:
            if depth[v] < 0:
                depth[v], parent[v] = depth[u] + 1, u
                queue.append(v)
            elif depth[v] == depth[u] + 1 and u < parent[v]:
                parent[v] = u
    return np.asarray(parent, np.int64)
