"""Batched vertex-program engine: the MS-BFS pipeline, generalized.

ScalaBFS's arbiter/apply/scatter pipeline is not BFS-specific — GraphScale
and fpgagraphlib-style frameworks run BFS, CC, SSSP and PageRank through
one scatter/apply skeleton with per-algorithm apply logic.  This module is
the software analogue: the level loop, the packed uint32 plane exchange,
the hybrid push/pull scheduler and the one-sync-per-level statvec protocol
are shared machinery, parameterized by a :class:`VertexProgram` bundle:

* ``init(g, roots) -> (frontier, seen, value)`` — seed one bit-plane per
  root plus the per-vertex value array the program accumulates into.
* ``commit(value, new_mask, lvl) -> value`` — the per-level apply: how a
  newly-discovered (vertex, plane) updates the value array (BFS/CC set the
  level on first reach; SSSP takes a min-plus relaxation; BFS parents
  take the candidate parent the step found, see ``payload``).
* ``combine`` — the plane merge op the fused propagate kernel and the
  distributed OR-reduce-scatter use ("or" for bit-planes; the kernel also
  implements "max" as the hook for payload planes — see
  ``kernels.msbfs_propagate``).
* ``done(statvec) -> bool`` — the convergence predicate, folded into the
  stacked per-level stats vector (no extra device round-trip).

The bit-plane trick transfers directly: a plane can carry a component seed
(CC) or a source id (SSSP hop-distance frontiers) just as well as a BFS
source, so every CSR/CSC edge read keeps serving the whole batch — the
software analogue of keeping all 32 HBM pseudo-channels busy.

Shipped instantiations: :class:`MultiSourceBFSRunner` (BFS, plus the
legacy bool-plane baseline), :class:`ConnectedComponentsRunner` (multi-
seed CC over the symmetrized graph), :class:`SSSPRunner` (batched
unit-weight shortest-path hop distances) and :class:`BFSParentsRunner`
(Graph 500 kernel 2: BFS parent trees).  All four inherit the packed-
word invariant (plane state never unpacks between P1 and the commit) and
the one-sync-per-level driver (``host_transfers == iterations + 2``).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial, wraps
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap
from repro.core.bfs_local import (INF, SV_COUNT, SV_MF, SV_MU, SV_NF,
                                  SV_NU, SV_OVERFLOW, SV_TOTAL, LocalGraph,
                                  compact_indices, count_traversed_edges,
                                  expand_edges, owner_path, validate_roots)
from repro.core.scheduler import (PUSH, SchedulerConfig, choose_mode,
                                  choose_mode_host)
from repro.spans import span

MODE_NAMES = ("push", "pull")    # by scheduler mode (PUSH, PULL)


# ---------------------------------------------------------------------------
# Algorithm bundles
# ---------------------------------------------------------------------------

def plane_seed_init(g: LocalGraph, roots: jax.Array):
    """Shared init: one bit-plane per root, value INF except 0 at the root.

    ``value`` is int32[n_pad, B] — levels for BFS/CC, hop distances for
    SSSP.  Frontier and seen start identical (the roots themselves).
    """
    b = roots.shape[0]
    planes = jnp.zeros((g.n_pad, b), jnp.bool_)
    planes = planes.at[roots, jnp.arange(b)].set(True)
    frontier = bitmap.pack_rows(planes)
    value = jnp.full((g.n_pad, b), INF, jnp.int32)
    value = value.at[roots, jnp.arange(b)].set(0)
    return frontier, frontier, value


def parent_seed_init(g: LocalGraph, roots: jax.Array):
    """Parent-plane init: the root planes of :func:`plane_seed_init`, and
    a value plane of parents, -1 except each root its own parent."""
    frontier, seen, _ = plane_seed_init(g, roots)
    b = roots.shape[0]
    value = jnp.full((g.n_pad, b), -1, jnp.int32)
    value = value.at[roots, jnp.arange(b)].set(roots)
    return frontier, seen, value


def level_commit(value, new_mask, lvl):
    """BFS/CC apply: a vertex first reached at level ``lvl+1`` keeps it."""
    return jnp.where(new_mask, lvl + 1, value)


def minplus_commit(value, new_mask, lvl):
    """SSSP (unit weights) apply: min-plus relaxation dist = min(dist,
    lvl+1) over newly-relaxed planes.  With unit weights first arrival IS
    the minimum, so this converges in the same level-synchronous sweeps."""
    return jnp.minimum(value, jnp.where(new_mask, lvl + 1, INF))


def parent_commit(value, new_mask, cand):
    """Parent apply: a (vertex, plane) first reached this level takes the
    candidate parent the step found (``cand``, see ``_edge_parents`` and
    ``_scan_parents``)."""
    return jnp.where(new_mask, cand, value)


def level_reached(value):
    return value < INF


def parent_reached(value):
    return value >= 0


def frontier_drained(sv: np.ndarray) -> bool:
    """Shared convergence predicate: no plane produced a new discovery."""
    return int(sv[SV_NF]) == 0


@dataclasses.dataclass(frozen=True)
class VertexProgram:
    """Per-algorithm bundle plugged into the shared engine.

    Frozen + module-level callables => hashable, so a program is a stable
    static jit argument (one compiled step per (program, budget, pallas)).
    ``undirected=True`` means the algorithm's semantics require the
    symmetrized graph (engine builders symmetrize before ``build_local_
    graph``; the engine itself is orientation-agnostic).

    ``payload="parents"`` makes the value plane a payload that rides the
    arcs: every step also finds, for each newly reached (vertex, plane),
    the smallest source id among the arcs that reached it, a min combine
    over int32 ids, and ``commit(value, new_mask, cand)`` receives those
    candidates in place of the level.  ``None`` (BFS, CC, SSSP) keeps the
    steps exactly as they are without it.  ``reached`` says which values
    of the finished rows count as reached (the TEPS numerator).
    """

    name: str
    init: Callable = plane_seed_init
    commit: Callable = level_commit
    done: Callable = frontier_drained
    combine: str = "or"          # plane merge op (see kernels.msbfs_propagate)
    undirected: bool = False
    reached: Callable = level_reached
    payload: str | None = None


BFS = VertexProgram(name="bfs")
CC = VertexProgram(name="cc", undirected=True)
SSSP = VertexProgram(name="sssp", commit=minplus_commit)
# Graph 500 kernel 2: each row is a BFS parent tree (root -> itself, -1
# where unreached, else the smallest-id in-neighbour one level up)
BFS_PARENTS = VertexProgram(name="bfs_parents", init=parent_seed_init,
                            commit=parent_commit, reached=parent_reached,
                            payload="parents")


class IntegrityError(RuntimeError):
    """A traversal integrity invariant was violated — the wave's answer
    cannot be trusted and must NOT be served.

    ScalaBFS trusts HBM ECC and a fixed PE pipeline to deliver correct
    frontier words; this software reproduction has no such guarantee, so
    the engine (``VertexProgramRunner`` with ``integrity != "off"``) folds
    cheap device-side invariant checks into the statvec protocol and
    raises this error the moment a check fails mid-run.  The serving
    supervisor (``repro.ft.EngineSupervisor``) classifies it as a
    KERNEL-CLASS transient fault: the wave is retried, and repeated
    violations walk the ``pallas -> jnp -> bool-plane`` demotion ladder —
    a corrupted kernel rung is the prime suspect.
    """


class BudgetOverflowError(RuntimeError):
    """Push edge budget still overflowed after ``max_overflow_retries``.

    By default the driver absorbs an overflowed (truncated) step silently
    by doubling the budget and re-running the level.  A serving deployment
    may prefer a bounded per-wave cost: with ``max_overflow_retries`` set,
    persistent overflow surfaces as this error carrying the last budget
    tried, so a fault-tolerance layer (``repro.ft.EngineSupervisor``) can
    retry the wave with an escalated starting budget instead of deepening
    inside the measured service time.
    """

    def __init__(self, budget: int, need: int, retries: int):
        super().__init__(
            f"push budget overflowed {retries}x (budget={budget}, "
            f"level needs ~{need} edges)")
        self.budget = int(budget)
        self.need = int(need)
        self.retries = int(retries)

PROGRAMS = {p.name: p for p in (BFS, CC, SSSP, BFS_PARENTS)}


def check_parent_rows(rows: np.ndarray, roots: np.ndarray) -> None:
    """Parent rows must hold -1 or a vertex id in ``[0, n)`` everywhere,
    and each plane's root must be its own parent.  Raises
    :class:`IntegrityError`."""
    rows = np.asarray(rows)
    roots = np.asarray(roots)
    bad = (rows < -1) | (rows >= rows.shape[1])
    if bad.any():
        b, v = (int(x) for x in np.argwhere(bad)[0])
        raise IntegrityError(
            f"{int(bad.sum())} parent values outside [-1, {rows.shape[1]})"
            f" (first: plane {b}, vertex {v}, value {int(rows[b, v])})")
    at_root = rows[np.arange(roots.size), roots]
    if np.any(at_root != roots):
        b = int(np.argwhere(at_root != roots)[0][0])
        raise IntegrityError(
            f"plane {b} lost its root: parent[{int(roots[b])}] = "
            f"{int(at_root[b])}, expected the root itself")


def get_program(name: str) -> VertexProgram:
    try:
        return PROGRAMS[name]
    except KeyError:
        raise ValueError(f"unknown vertex program {name!r}; "
                         f"have {sorted(PROGRAMS)}") from None


# ---------------------------------------------------------------------------
# Shared packed-plane machinery (the extracted MS-BFS hot path).
#
# Frontier/seen state is a per-vertex PLANE mask — bit b of row v says
# "plane b has reached v" — packed into uint32[n_pad, ceil(B/32)] words
# (bitmap.pack_rows).  Every CSR/CSC edge read is shared by the whole
# batch: propagating along an edge is one 32/64-bit combine instead of B
# separate traversals (MS-BFS sharing; Then et al., VLDB'14).
#
# The packed words are the ONLY state representation: push gathers the
# frontier words of budgeted edges and scatter-combines them into the
# candidate words (Pallas msbfs_propagate / bitmap._scatter_or_rows);
# pull reduces each vertex's in-list with a segmented OR-scan over the
# static CSC edge stream (bitmap.segment_or_rows) — no unpack, no bool
# plane arrays, no scatter buffers.
# ---------------------------------------------------------------------------

# index of the OPTIONAL integrity slot appended to the statvec when a
# runner has integrity checking on (the base int32[7] layout lives in
# bfs_local; slot presence is a static jit choice, so clean runs pay it
# neither in compute nor in transfer width)
SV_CHECK = 7

# runner integrity levels, strictly ordered by cost:
#   off        — no checks (the historical engine)
#   invariants — device-side statvec invariants + host popcount/row checks
#   witness    — invariants + per-wave sampled parent-witness reduction
#   audit      — witness at engine level; the supervisor additionally
#                rate-samples a full differential audit against a
#                reference path (see repro.ft.integrity)
INTEGRITY_MODES = ("off", "invariants", "witness", "audit")


def _integrity_chk(frontier_w, seen_w, nb: int):
    """Device-side plane-word invariant residue (0 on an uncorrupted run).

    Three invariants the packed pipeline maintains by construction, folded
    into one popcount so the statvec grows by a single int32 slot:

    * ``frontier ⊆ seen`` — every step's frontier is last step's ``new``,
      which was OR-ed into ``seen`` in the same kernel.  A flipped plane
      word that conjures a frontier bit for an unseen vertex breaks this.
    * frontier pad bits beyond the true batch width are zero.
    * seen pad bits beyond the true batch width are zero.
    """
    pmask = bitmap.plane_mask(nb)
    return (bitmap.popcount(frontier_w & ~seen_w)
            + bitmap.popcount(frontier_w & ~pmask)
            + bitmap.popcount(seen_w & ~pmask))


def _vp_statvec(g: LocalGraph, new_w, seen_w, total, overflow, nb: int,
                chk=None, hits=None):
    """Fused per-level stats: scheduler inputs for the NEXT level, this
    step's edge total/overflow, and the discovery popcount, stacked into
    one int32[7] so the driver fetches a single array per level (int32[8]
    with the integrity residue ``chk`` appended when checking is on; a
    payload program appends last the rows its step wrote, ``hits``).

    ``nb`` is the TRUE batch size: the pad planes of the last word are
    unseen by construction, so masking with the padded width would make
    every vertex count as "unseen by some plane" forever."""
    pmask = bitmap.plane_mask(nb)
    any_f = bitmap.any_rows(new_w)
    un_any = bitmap.any_rows(~seen_w & pmask)
    slots = [
        jnp.sum(any_f, dtype=jnp.int32),
        jnp.sum(jnp.where(any_f, g.out_deg, 0), dtype=jnp.int32),
        jnp.sum(jnp.where(un_any, g.in_deg, 0), dtype=jnp.int32),
        jnp.sum(un_any, dtype=jnp.int32),
        jnp.asarray(total, jnp.int32),
        jnp.asarray(overflow, jnp.int32),
        bitmap.popcount(new_w),
    ]
    if chk is not None:
        slots.append(jnp.asarray(chk, jnp.int32))
    if hits is not None:
        slots.append(jnp.asarray(hits, jnp.int32))
    return jnp.stack(slots)


def _vp_commit(g: LocalGraph, program: VertexProgram, new_w, seen_w, value,
               lvl, total, overflow, chk=None, payload=None):
    """Per-level apply (the pipeline's single unpack point) + fused stats.
    ``payload`` is a payload program's ``(cand, hits)``."""
    new_mask = bitmap.unpack_rows(new_w, value.shape[1])
    if payload is None:
        value2, hits = program.commit(value, new_mask, lvl), None
    else:
        cand, hits = payload
        value2 = program.commit(value, new_mask, cand)
    return value2, _vp_statvec(g, new_w, seen_w, total, overflow,
                               value.shape[1], chk, hits)


# a candidate-parent slot that no arc wrote
NO_PARENT = np.iinfo(np.int32).max
# payload rows written per pass of the parent loop: the update is at most
# [PARENT_CHUNK, B] int32 whatever a level finds, and a pass costs about
# its full size on the chip (rows past the count are dropped, not skipped)
PARENT_CHUNK = 1 << 20


def _write_parents(g: LocalGraph, hit, src, tgt, words, nb: int):
    """Candidate parents from a list of arcs: ``cand[tgt[i], p]`` = the
    smallest ``src[i]`` over the listed ``i`` with ``hit[i]`` and bit
    ``p`` of ``words[i]``, ``NO_PARENT`` where no such arc is.

    The hit arcs are compacted (one sort of the list's positions, hits
    first) and scatter-min'ed ``PARENT_CHUNK`` rows a pass in a device
    loop, so the step has one shape whatever a level finds and its update
    is never ``[list, B]``.  Returns ``(cand, hits)``."""
    m = hit.shape[0]
    pos = jnp.arange(m, dtype=jnp.int32)
    order = jax.lax.sort(jnp.where(hit, pos, pos + m))
    hits = jnp.sum(hit, dtype=jnp.int32)
    chunk = min(PARENT_CHUNK, m)

    def write(k, cand):
        # the last pass's start is clamped so it stays in bounds: it
        # re-writes some rows of the pass before, which min makes harmless
        i = jax.lax.dynamic_slice(order, (k * chunk,), (chunk,))
        ok = i < m
        i = jnp.where(ok, i, 0)
        ids = jnp.where(bitmap.unpack_rows(words[i], nb), src[i][:, None],
                        NO_PARENT)
        return cand.at[jnp.where(ok, tgt[i], g.n_pad)].min(ids, mode="drop")

    cand = jnp.full((g.n_pad, nb), NO_PARENT, jnp.int32)
    return jax.lax.fori_loop(0, (hits + chunk - 1) // chunk, write,
                             cand), hits


def _edge_parents(g: LocalGraph, frontier_w, new_w, src, tgt, valid,
                  nb: int):
    """Candidate parents over an edge list (push, budgeted pulls): each
    listed arc whose source is in the frontier of a plane its target is
    new in writes its source id there."""
    words = (frontier_w[jnp.maximum(src, 0)]
             & new_w[jnp.where(valid, tgt, 0)])
    hit = valid & bitmap.any_rows(words)
    return _write_parents(g, hit, jnp.maximum(src, 0), tgt, words, nb)


def _scan_parents(g: LocalGraph, msg, scan, new_w, nb: int):
    """Candidate parents read off the dense pull's segmented OR-scan.

    An arc is a first hit for plane ``p`` when its source is in ``p``'s
    frontier (``msg``), no earlier arc of the same in-list's is (the scan
    one slot back, within the segment) and its child is new in ``p``: one
    arc per new (vertex, plane), which is the smallest-id frontier
    in-neighbour because in-lists are sorted by source
    (``build_local_graph``).  ``new`` reaches the arcs by a second scan
    of the child's words set at its in-list's first arc (a gather of
    ``new`` over every arc costs the chip about as much as the pull's own
    gather).  Only the first-hit arcs are written (``_write_parents``)."""
    if msg is None:                 # a graph without arcs
        return jnp.full((g.n_pad, nb), NO_PARENT, jnp.int32), jnp.int32(0)
    e = msg.shape[0]
    same = (g.in_child[1:] == g.in_child[:-1])[:, None]
    excl = jnp.concatenate([jnp.zeros_like(scan[:1]),
                            jnp.where(same, scan[:-1], jnp.uint32(0))])
    starts = jnp.where(g.in_deg > 0, g.in_indptr[:-1], e)
    new_arc = bitmap.segment_or_rows(
        jnp.zeros_like(msg).at[starts].set(new_w, mode="drop"), g.in_child)
    first = msg & ~excl & new_arc
    return _write_parents(g, bitmap.any_rows(first), g.in_indices,
                          g.in_child, first, nb)


def _propagate_edges(g: LocalGraph, frontier_w, seen_w, src, tgt, valid,
                     use_pallas: bool, combine: str = "or",
                     tile_rows: int | None = None):
    """Fused P2->P3 on packed words: cand[tgt] ⊕= frontier[src], then
    new = cand & ~seen, seen |= new.  Pallas kernel or jnp fallback.
    ``tile_rows`` selects the kernel variant (None = auto by plane-array
    footprint, 0 = whole-VMEM, > 0 = row-tiled at that size)."""
    if use_pallas:
        from repro.kernels import ops as kops
        new, seen2, _ = kops.msbfs_propagate(frontier_w, seen_w, src, tgt,
                                             valid, op=combine,
                                             tile_rows=tile_rows)
        return new, seen2
    if combine != "or":
        raise NotImplementedError(
            f"jnp fallback implements combine='or' only, got {combine!r} "
            "(payload-plane combines run through the Pallas kernel)")
    msg = frontier_w[jnp.maximum(src, 0)]
    cand = bitmap._scatter_or_rows(
        jnp.zeros_like(frontier_w), jnp.where(valid, tgt, g.n_pad), msg)
    new = cand & ~seen_w
    return new, seen_w | new


def _propagate_pull_scan(g: LocalGraph, frontier_w):
    """Candidate plane words for ALL vertices via the CSC edge stream:
    cand[v] = OR of frontier[parent] over v's in-list.  The edges are
    already grouped by child, so a segmented OR-scan + one gather at the
    segment ends replaces the scatter entirely (packed words throughout).
    Returns ``(cand, msg, scan)``; the last two (None without arcs) are
    the per-arc words and their scan, which the parent payload reads."""
    if g.in_indices.shape[0] == 0:
        return jnp.zeros_like(frontier_w), None, None
    msg = frontier_w[g.in_indices]                  # [E, nw] packed gather
    scan = bitmap.segment_or_rows(msg, g.in_child)
    return jnp.where((g.in_seg_end >= 0)[:, None],
                     scan[jnp.maximum(g.in_seg_end, 0)],
                     jnp.uint32(0)), msg, scan


def _propagate_pull_sparse(g: LocalGraph, frontier_w, seen_w, nb: int,
                           budget: int):
    """Budgeted pull: expand ONLY some-plane-unseen vertices' in-lists.

    The dense scan pull re-reads the whole CSC stream every level even when
    almost every vertex is already seen; the paper's pull reads just the
    unvisited vertices' in-lists (bounded by m_u).  This is the jnp
    analogue: expand_edges over the unseen-any set is vertex-major, so the
    segment boundaries fall out of the cumulative degrees and the same
    segmented OR-scan reduces each in-list — over ``budget`` edges instead
    of E.  Pays off on tail levels where m_u << E; the driver keeps the
    dense scan for full-stream levels (the expansion bookkeeping costs
    more per edge than the static-boundary scan).

    Returns (new, seen2, total, edges); ``total > budget`` means the step
    was truncated and must be retried deeper (same overflow contract as
    push); ``edges`` is the ``(parent, child, valid)`` list it expanded.
    """
    pmask = bitmap.plane_mask(nb)
    un_any = bitmap.any_rows(~seen_w & pmask)
    active, _ = compact_indices(un_any, g.n_pad)
    a = jnp.maximum(active, 0)
    deg = (g.in_indptr[a + 1] - g.in_indptr[a]) * (active >= 0)
    cum = jnp.cumsum(deg)
    total = cum[-1]
    e = jnp.arange(budget, dtype=jnp.int32)
    owner = jnp.searchsorted(cum, e, side="right").astype(jnp.int32)
    owner_c = jnp.minimum(owner, active.shape[0] - 1)
    start = cum[owner_c] - deg[owner_c]
    child = active[owner_c]
    eidx = g.in_indptr[jnp.maximum(child, 0)] + (e - start)
    valid = e < total
    parent = g.in_indices[jnp.where(valid, eidx, 0)]
    msg = jnp.where(valid[:, None], frontier_w[parent], jnp.uint32(0))
    scan = bitmap.segment_or_rows(msg, owner_c)
    # one segment end per active vertex -> unique scatter targets, so a
    # plain row set (mode="drop" for the pad slots) lands the per-vertex OR
    endpos = jnp.clip(cum - 1, 0, budget - 1)
    rows = jnp.where((deg > 0) & (active >= 0), active, g.n_pad)
    cand = jnp.zeros((g.n_pad + 1, frontier_w.shape[1]), jnp.uint32)
    cand = cand.at[rows].set(scan[endpos], mode="drop")[:-1]
    new = cand & ~seen_w
    return new, seen_w | new, total, (parent, child, valid)


@partial(jax.jit, static_argnames=("program",))
def _plane_traversed(g: LocalGraph, value, program: VertexProgram = BFS):
    """int32[B]: per-plane traversed edges = sum of out-degrees over the
    vertices each plane reached (the paper's TEPS numerator, one entry per
    source so pad planes can be sliced off without a host recount)."""
    reached = program.reached(value[: g.n])
    return jnp.sum(jnp.where(reached, g.out_deg[: g.n, None], 0),
                   axis=0, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("budget", "parents"))
def _witness_check(g: LocalGraph, value, sample, budget: int,
                   parents: bool = False):
    """Sampled parent-witness audit, one fused reduction.

    For every sampled vertex ``v`` and plane ``p`` with a finite non-root
    value, SOME in-neighbor ``u`` must hold ``value[u,p] == value[v,p]-1``
    — the parent that discovered it (level-synchronous BFS/CC and
    unit-weight SSSP all satisfy this exactly).  On parent rows
    (``parents``) the witness is the parent itself: for every reached
    non-root ``v``, ``value[v,p]`` must be an in-neighbour ``u`` of ``v``
    that is reached in ``p`` (``value[u,p] >= 0``).  The K sampled
    in-lists are expanded with the same budgeted owner-slot pattern as
    the sparse pull, the witness predicate is OR-reduced per (vertex,
    plane), and the result collapses to int32[2] = (violations,
    truncated) so it folds into the run's final fetch
    (``host_transfers`` invariant intact).
    ``truncated != 0`` means the sampled in-lists overflowed ``budget``
    and the violation count is unusable — the driver skips, not raises.
    """
    k = sample.shape[0]
    deg = g.in_indptr[sample + 1] - g.in_indptr[sample]
    cum = jnp.cumsum(deg)
    total = cum[-1]
    e = jnp.arange(budget, dtype=jnp.int32)
    owner = jnp.searchsorted(cum, e, side="right").astype(jnp.int32)
    owner_c = jnp.minimum(owner, k - 1)
    start = cum[owner_c] - deg[owner_c]
    child = sample[owner_c]
    eidx = g.in_indptr[child] + (e - start)
    valid = (e < total) & (e < jnp.int32(budget))
    parent = g.in_indices[jnp.where(valid, eidx, 0)]
    if parents:
        ok_e = valid[:, None] & (value[child] == parent[:, None]) & (
            value[parent] >= 0)
    else:
        ok_e = valid[:, None] & (value[parent] == value[child] - 1)
    ok = jnp.zeros((k + 1, value.shape[1]), jnp.bool_)
    ok = ok.at[jnp.where(valid, owner_c, k)].max(ok_e, mode="drop")[:-1]
    vals = value[sample]                              # [K, B]
    need = ((vals >= 0) & (vals != sample[:, None]) if parents
            else (vals > 0) & (vals < INF))
    return jnp.stack([jnp.sum(need & ~ok, dtype=jnp.int32),
                      jnp.asarray(total > budget, jnp.int32)])


def _xor_plane_bit(words, vertex: int, plane: int):
    """Flip one bit of one packed plane word (the chaos layer's HBM
    bit-flip analogue; see ``repro.ft.FaultyEngine``).  XOR, not OR: a
    flip of a set bit suppresses a discovery rather than conjuring one."""
    word, bit = divmod(int(plane), bitmap.WORD_BITS)
    return words.at[int(vertex), word].set(
        words[int(vertex), word] ^ jnp.uint32(1 << bit))


@partial(jax.jit, static_argnames=("program", "check"))
def vp_init_state(g: LocalGraph, roots: jax.Array, program: VertexProgram,
                  check: bool = False):
    frontier, seen, value = program.init(g, roots)
    chk = (_integrity_chk(frontier, seen, roots.shape[0]) if check
           else None)
    return (frontier, seen, value,
            _vp_statvec(g, frontier, seen, 0, 0, roots.shape[0], chk))


def _push(g: LocalGraph, frontier_w, seen_w, value, lvl,
          program: VertexProgram, budget: int,
          use_pallas: bool = False, tile_rows: int | None = None,
          check: bool = False):
    """Batched push on packed words: expand out-lists of any-plane
    frontier vertices; each budgeted edge carries its endpoint's packed
    plane word straight into the candidate planes (fused P2->P3).  A
    parent payload writes each budgeted edge's source id where the edge
    reaches a new (vertex, plane) besides."""
    # the integrity residue is computed from the step's INPUT state: it
    # rides the output statvec but indicts the words the step consumed
    chk = (_integrity_chk(frontier_w, seen_w, value.shape[1]) if check
           else None)
    any_f = bitmap.any_rows(frontier_w)
    active, _ = compact_indices(any_f, g.n_pad)
    src, nbr, valid, total = expand_edges(active, g.out_indptr,
                                          g.out_indices, budget)
    new, seen2 = _propagate_edges(g, frontier_w, seen_w, src, nbr, valid,
                                  use_pallas, program.combine, tile_rows)
    payload = None
    if program.payload:
        payload = _edge_parents(g, frontier_w, new, src, nbr, valid,
                                value.shape[1])
    value2, statvec = _vp_commit(g, program, new, seen2, value, lvl, total,
                                 total > budget, chk, payload)
    return new, seen2, value2, statvec


def _pull(g: LocalGraph, frontier_w, seen_w, value, lvl,
          program: VertexProgram, budget: int = 0,
          use_pallas: bool = False, tile_rows: int | None = None,
          check: bool = False):
    """Batched pull on packed words.

    Default path (``budget == 0``): dense segmented OR-scan over the whole
    CSC edge stream (never overflows); a parent payload reads its
    candidates off that scan (``_scan_parents``).  ``budget > 0`` selects
    the sparse budgeted pull — only some-plane-unseen vertices' in-lists
    are expanded (``_propagate_pull_sparse``), which the driver uses on
    tail levels where m_u << E.  Pallas path: budgeted expansion through
    the fused propagate kernel.  The budgeted pulls find parents over the
    edges they expanded, as push does."""
    nb = value.shape[1]
    chk = _integrity_chk(frontier_w, seen_w, nb) if check else None
    edges = None
    if use_pallas:
        un_any = bitmap.any_rows(~seen_w & bitmap.plane_mask(nb))
        active, _ = compact_indices(un_any, g.n_pad)
        child, parent, valid, total = expand_edges(
            active, g.in_indptr, g.in_indices, budget)
        new, seen2 = _propagate_edges(g, frontier_w, seen_w, parent, child,
                                      valid, True, program.combine,
                                      tile_rows)
        edges = (parent, child, valid)
        overflow = total > budget
    elif budget:
        new, seen2, total, edges = _propagate_pull_sparse(
            g, frontier_w, seen_w, nb, budget)
        overflow = total > budget
    else:
        cand, msg, scan = _propagate_pull_scan(g, frontier_w)
        new = cand & ~seen_w
        seen2 = seen_w | new
        total = jnp.int32(g.in_indices.shape[0])
        overflow = jnp.int32(0)
    payload = None
    if program.payload and edges is None:
        payload = _scan_parents(g, msg, scan, new, nb)
    elif program.payload:
        payload = _edge_parents(g, frontier_w, new, *edges, nb)
    value2, statvec = _vp_commit(g, program, new, seen2, value, lvl, total,
                                 overflow, chk, payload)
    return new, seen2, value2, statvec


def _jit_step(body, name: str):
    """``body`` jitted under its own name: the device trace names each
    program after the function jit wraps, so the parent program's steps
    (``*_parents``, the same bodies) read apart from the others'."""
    fn = wraps(body)(lambda *a, **kw: body(*a, **kw))
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, static_argnames=("program", "budget", "use_pallas",
                                        "tile_rows", "check"))


vp_push_step = _jit_step(_push, "vp_push_step")
vp_pull_step = _jit_step(_pull, "vp_pull_step")
vp_push_step_parents = _jit_step(_push, "vp_push_step_parents")
vp_pull_step_parents = _jit_step(_pull, "vp_pull_step_parents")


def vp_reference(g: LocalGraph, roots, program: VertexProgram = BFS,
                 max_iters: int | None = None):
    """Fully-jit dense vertex-program loop (packed words, pull-form
    edge-parallel steps).  Returns the finalized value rows [B, n]."""
    roots = jnp.asarray(roots, jnp.int32)
    max_iters = max_iters or g.n_pad
    frontier0, seen0, value0 = program.init(g, roots)

    def cond(state):
        frontier, seen, value, lvl = state
        return (bitmap.popcount(frontier) > 0) & (lvl < max_iters)

    def body(state):
        frontier, seen, value, lvl = state
        cand, msg, scan = _propagate_pull_scan(g, frontier)
        new = cand & ~seen
        seen = seen | new
        new_mask = bitmap.unpack_rows(new, roots.shape[0])
        if program.payload:
            parents, _ = _scan_parents(g, msg, scan, new, roots.shape[0])
            value = program.commit(value, new_mask, parents)
        else:
            value = program.commit(value, new_mask, lvl)
        return new, seen, value, lvl + 1

    frontier, seen, value, lvl = jax.lax.while_loop(
        cond, body, (frontier0, seen0, value0, jnp.int32(0)))
    return value[: g.n].T


def msbfs_reference(g: LocalGraph, roots, max_iters: int | None = None):
    """Fully-jit dense MS-BFS loop (packed words).  Returns level [B, n]."""
    return vp_reference(g, roots, BFS, max_iters)


# ---------------------------------------------------------------------------
# Results + the generic one-sync-per-level driver
# ---------------------------------------------------------------------------

def _next_pow2(x: int) -> int:
    """Smallest power of two at or above ``x`` (1 for ``x <= 1``)."""
    return 1 << max(0, int(x) - 1).bit_length()


@dataclasses.dataclass
class VertexProgramResult:
    levels: np.ndarray          # int32[B, n] — one value row per plane
    batch: int
    iterations: int
    # edges actually streamed per level.  NOTE: the packed pipeline's
    # scan-based pull reads the WHOLE CSC edge stream per pull level
    # (that is its cost model), so this is not comparable edge-for-edge
    # with the budgeted bool-plane baseline's m_u-bounded pulls.
    edges_inspected: int
    push_iters: int
    pull_iters: int
    traversed_edges: int        # summed over all planes (paper §VI-A metric)
    seconds: float
    host_transfers: int = 0     # blocking device->host fetches during run
    algo: str = "bfs"
    labels: np.ndarray | None = None   # CC: int64[n] min-seed labels
    overflow_retries: int = 0   # levels re-run after a truncated push/pull
    budget: int = 0             # largest edge budget a level of the run used

    @property
    def distances(self) -> np.ndarray:
        """SSSP alias: the value rows are hop distances."""
        return self.levels

    @property
    def parents(self) -> np.ndarray:
        """BFS-parents alias: the value rows are parent trees."""
        return self.levels

    @property
    def aggregate_teps(self) -> float:
        return self.traversed_edges / max(self.seconds, 1e-12)

    @property
    def gteps(self) -> float:
        return self.aggregate_teps / 1e9


# Backwards-compatible name: BFS results are the same record.
MSBFSResult = VertexProgramResult


class VertexProgramRunner:
    """Python-driven hybrid vertex-program engine over a batch of roots.

    The per-iteration structure is the paper's pipeline (stats -> mode ->
    gather/scan step -> P3 commit) with one bit-plane per root; direction
    choice uses any-plane frontier / any-plane-unseen statistics.  Plane
    state never unpacks between P1 and the commit, and each level costs
    exactly one blocking device->host transfer (the fused stats vector):
    ``result.host_transfers == iterations + 2``.

    ``run`` is the SHARED entry for every algorithm: it validates the
    roots once (negative / >= |V| roots would scatter silently out of
    bounds) so no instantiation can forget to.
    """

    program: VertexProgram = BFS

    def __init__(self, g: LocalGraph, program: VertexProgram | None = None,
                 sched: SchedulerConfig | None = None,
                 init_budget: int = 1 << 15, use_pallas: bool = False,
                 max_overflow_retries: int | None = None,
                 tile_rows: int | None = None, sparse_pull: bool = False,
                 integrity: str = "off", witness_k: int = 64,
                 witness_budget: int = 4096,
                 integrity_seed: int | None = 0):
        if integrity not in INTEGRITY_MODES:
            raise ValueError(f"integrity must be one of {INTEGRITY_MODES}, "
                             f"got {integrity!r}")
        self.g = g
        self.program = program if program is not None else type(self).program
        self.sched = sched or SchedulerConfig()
        self.init_budget = init_budget
        self.use_pallas = use_pallas
        # per-wave integrity validation (see INTEGRITY_MODES).  Mutable
        # between waves: the serving supervisor flips it on the engine it
        # wraps.  "audit"'s differential re-run lives in the supervisor;
        # at engine level it behaves like "witness".
        self.integrity = integrity
        self.witness_k = witness_k
        self.witness_budget = witness_budget
        self._witness_rng = np.random.default_rng(integrity_seed)
        # exact-once plane corruption hook: (level, vertex, plane) set by
        # the chaos layer (repro.ft.FaultyEngine) to XOR one frontier bit
        # right before that level's step; consumed (or cleared) per run
        self._corrupt_plane: tuple[int, int, int] | None = None
        # Pallas propagate variant: None = auto by plane-array footprint
        # (kernels.ops.propagate_plan), 0 = force whole-VMEM, > 0 = force
        # row tiles of that many vertices
        self.tile_rows = tile_rows
        # budgeted pull on tail levels where m_u is far below the full CSC
        # stream (see _propagate_pull_sparse); off by default to preserve
        # the dense scan's cost model (edges_inspected counts E per pull)
        self.sparse_pull = sparse_pull
        # None = deepen forever (absorb overflow silently, the historical
        # behavior); an int bounds per-wave re-runs and surfaces persistent
        # overflow as BudgetOverflowError for the serving FT layer
        self.max_overflow_retries = max_overflow_retries
        self._transfers = 0
        self.last_stats: dict = {}
        # fetched once here so the TEPS accounting after each run is not
        # an extra (uncounted) device->host transfer
        self._out_deg_np = np.asarray(g.out_deg)[: g.n]

    # -- engine protocol --------------------------------------------------
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

    def _sync(self, statvec, level: int, retry: int) -> np.ndarray:
        """The blocking statvec fetch after the init (level 0) or a step."""
        with span("vp.sync", level=level, retry=retry):
            return self._fetch(statvec)

    def _fetch_pair(self, a, b):
        """One blocking device->host round trip for two device values."""
        self._transfers += 1
        return jax.device_get((a, b))

    def _fetch_many(self, *vals):
        """One blocking device->host round trip for N device values (the
        final fetch grows a witness verdict without a second sync)."""
        self._transfers += 1
        return jax.device_get(vals)

    # -- integrity guards (active when ``integrity != "off"``) ------------
    def _guard_sv(self, sv: np.ndarray, lvl: int, nb: int,
                  discovered: int) -> None:
        """Host-side checks on the just-fetched statvec: the device-side
        residue slot, frontier-count/popcount agreement, discovery-total
        bound and loop-termination bound.  Raises IntegrityError."""
        if int(sv[SV_CHECK]) != 0:
            raise IntegrityError(
                f"plane-word invariant violated at level {lvl}: "
                f"{int(sv[SV_CHECK])} corrupt frontier/seen/pad bits "
                "(frontier ⊄ seen or dirty pad bits)")
        if (int(sv[SV_NF]) > 0) != (int(sv[SV_COUNT]) > 0):
            raise IntegrityError(
                f"statvec inconsistent at level {lvl}: frontier rows "
                f"{int(sv[SV_NF])} vs discovery popcount "
                f"{int(sv[SV_COUNT])}")
        if discovered + int(sv[SV_COUNT]) > self.g.n * nb:
            raise IntegrityError(
                f"cumulative discoveries {discovered + int(sv[SV_COUNT])} "
                f"exceed |V| x planes = {self.g.n * nb} at level {lvl} "
                "(each (vertex, plane) pair can be discovered once)")
        if lvl > self.g.n:
            raise IntegrityError(
                f"nonterminating traversal: level {lvl} exceeds |V| = "
                f"{self.g.n} (discovery popcounts must drain within n "
                "levels)")

    def _guard_rows(self, rows: np.ndarray, roots: np.ndarray,
                    iters: int) -> None:
        """Final value rows must be 0 at each plane's own root and either
        INF or bounded by the iteration count everywhere else (parent
        rows: :func:`check_parent_rows`)."""
        if self.program.payload == "parents":
            check_parent_rows(rows, roots)
            return
        bad = (rows != int(INF)) & ((rows < 0) | (rows > iters))
        if bad.any():
            v = int(np.argwhere(bad)[0][1])
            raise IntegrityError(
                f"{int(bad.sum())} result values outside "
                f"[0, {iters}] ∪ {{INF}} (first at vertex {v})")
        at_root = rows[np.arange(roots.size), roots]
        if np.any(at_root != 0):
            raise IntegrityError(
                f"{int(np.sum(at_root != 0))} planes lost their root "
                "(value at own root != 0)")

    def _pull_budget(self, m_u: int) -> int:
        """Sparse-pull budget for this level, or 0 to keep the dense scan.

        ``m_u`` bounds the expansion exactly (every some-plane-unseen
        vertex contributes its whole in-list), so the next power of two
        above it can never overflow.  The sparse path's per-edge cost is
        several times the static-boundary scan's, so it only engages well
        below the full CSC stream — full-ish levels stay dense."""
        cap = int(self.g.in_indices.shape[0])
        pb = max(1 << 12, _next_pow2(m_u))
        return pb if pb * 8 <= cap else 0

    def _owner_meta(self, expands: bool, budget: int) -> dict:
        """``{"owner": "merge" | "search"}``, the owner path of the
        level's ``expand_edges`` (over all ``n_pad`` vertex slots), for a
        step that expands; else empty."""
        return ({"owner": owner_path(self.g.n_pad, budget)} if expands
                else {})

    def run(self, roots, *, budget: int | None = None) -> VertexProgramResult:
        # validate BEFORE the int32 cast: a >= 2**31 root must error, not
        # wrap.  This is the shared entry — every algorithm goes through it.
        roots = validate_roots(np.asarray(roots), self.g.n).astype(np.int32)
        self._transfers = 0
        return self._finalize(self._run_packed(roots, budget), roots)

    def run_batch(self, roots, *, budget: int | None = None) -> np.ndarray:
        """Engine-protocol entry: value rows [B, n] + ``last_stats``.

        ``budget`` overrides ``init_budget`` for THIS wave only: it is the
        floor of every budgeted level's edge budget, each level running at
        the larger of the floor and the next power of two at or above its
        need.  The serving supervisor uses it to escalate the edge budget
        on a retry after persistent push-budget overflow, without re-tuning
        the engine's steady-state starting point.
        """
        return self.run(roots, budget=budget).levels

    def _finalize(self, res: VertexProgramResult,
                  roots: np.ndarray) -> VertexProgramResult:
        """Per-algorithm post-processing hook (e.g. CC labels)."""
        return res

    # -- the extracted one-sync-per-level loop ----------------------------
    def _run_packed(self, roots: np.ndarray,
                    budget_override: int | None = None
                    ) -> VertexProgramResult:
        g = self.g
        # no point budgeting past the whole edge array (keeps the budgeted
        # kernels small on tiny graphs); the overflow loop still deepens
        floor = min(budget_override or self.init_budget,
                    max(g.out_indices.shape[0], g.in_indices.shape[0]) + 1)
        with span("vp.wave", slots=int(roots.size), budget=floor):
            return self._traverse(roots, floor)

    def _traverse(self, roots: np.ndarray,
                  floor: int) -> VertexProgramResult:
        g, program = self.g, self.program
        b = int(roots.size)
        check = self.integrity != "off"
        witness = self.integrity in ("witness", "audit")
        corrupt, self._corrupt_plane = self._corrupt_plane, None
        pcs: list[int] = []         # per-level discovery popcounts
        levels: list[dict] = []     # per-level counters (last_stats)
        mode = PUSH
        lvl = 0
        inspected = 0
        push_iters = pull_iters = 0
        overflow_retries = 0
        budget = floor              # largest budget any level used
        payload_meta = ({"payload": program.payload} if program.payload
                        else {})
        t0 = time.perf_counter()
        with span("vp.init"):
            frontier, seen, value, statvec = vp_init_state(
                g, jnp.asarray(roots), program, check=check)
        sv = self._sync(statvec, 0, 0)
        if check:
            self._guard_sv(sv, 0, b, 0)
        pcs.append(int(sv[SV_COUNT]))
        while not program.done(sv):
            # vp.level.host: the host's work from the statvec to the return
            # of the next step's dispatch
            with span("vp.level.host", level=lvl, retry=0,
                      **payload_meta) as host:
                mode = choose_mode_host(self.sched, mode, int(sv[SV_NF]),
                                        int(sv[SV_MF]), int(sv[SV_MU]), g.n,
                                        int(sv[SV_NU]))
                need = int(sv[SV_MF]) if mode == PUSH else int(sv[SV_MU])
                # the scan-based pull is dense over the CSC edge stream:
                # only push (and the budgeted Pallas/sparse pulls) need a
                # budget
                budgeted = mode == PUSH or self.use_pallas
                step_budget = 0
                if budgeted:
                    # ``need`` is exactly what the step expands, so the
                    # next power of two at or above it cannot overflow;
                    # each level picks its own rung, so tail levels run
                    # small after a wide one
                    cap = (g.out_indices if mode == PUSH
                           else g.in_indices).shape[0]
                    step_budget = max(floor, _next_pow2(min(need, cap + 1)))
                elif self.sparse_pull:
                    step_budget = self._pull_budget(int(sv[SV_MU]))
                if program.payload:
                    step = (vp_push_step_parents if mode == PUSH
                            else vp_pull_step_parents)
                else:
                    step = vp_push_step if mode == PUSH else vp_pull_step
                if corrupt is not None and lvl == int(corrupt[0]):
                    # chaos hook: flip one frontier plane bit, exact-once
                    frontier = _xor_plane_bit(frontier, corrupt[1],
                                              corrupt[2])
                    corrupt = None
                # retry from the PRE-step seen: an overflowed (truncated)
                # step may have committed a partial discovery set
                state0 = (frontier, seen, value)
                host.set_metadata(mode=MODE_NAMES[mode], budget=step_budget,
                                  **self._owner_meta(budgeted, step_budget))
                frontier, seen, value, statvec = step(
                    g, *state0, np.int32(lvl), program, step_budget,
                    self.use_pallas, self.tile_rows, check=check)
            sv = self._sync(statvec, lvl, 0)
            retries = 0
            while step_budget and bool(sv[SV_OVERFLOW]):
                retries += 1
                with span("vp.level.host", level=lvl, retry=retries,
                          mode=MODE_NAMES[mode], **payload_meta) as host:
                    if check:
                        self._guard_sv(sv, lvl, b, sum(pcs))
                    overflow_retries += 1   # surfaced in last_stats
                    if (self.max_overflow_retries is not None
                            and overflow_retries > self.max_overflow_retries):
                        raise BudgetOverflowError(step_budget, int(sv[SV_MF]),
                                                  overflow_retries)
                    step_budget *= 2   # HBM-reader queue overflow: deepen
                    host.set_metadata(budget=step_budget,
                                      **self._owner_meta(budgeted,
                                                         step_budget))
                    frontier, seen, value, statvec = step(
                        g, *state0, np.int32(lvl), program, step_budget,
                        self.use_pallas, self.tile_rows, check=check)
                sv = self._sync(statvec, lvl, retries)
            if check:
                self._guard_sv(sv, lvl, b, sum(pcs))
            if budgeted:
                budget = max(budget, step_budget)
            levels.append(dict(mode=MODE_NAMES[mode], budget=step_budget,
                               need=need, total=int(sv[SV_TOTAL]),
                               retries=retries,
                               **self._owner_meta(budgeted, step_budget)))
            if program.payload:
                # the arcs the step's payload wrote: edges that reach a
                # new (vertex, plane), or the dense pull's first hits
                levels[-1]["hits"] = int(sv[-1])
            pcs.append(int(sv[SV_COUNT]))
            lvl += 1
            inspected += int(sv[SV_TOTAL])
            if mode == PUSH:
                push_iters += 1
            else:
                pull_iters += 1
        with span("vp.rows", slots=b):
            value.block_until_ready()
            dt = time.perf_counter() - t0
            # per-plane traversed-edge counts, computed ON DEVICE and fetched
            # with the value rows in ONE blocking transfer (host_transfers
            # stays iterations + 2).  Each plane's count is <= E so int32 is
            # safe; the cross-plane sum happens on host in int64.  The numpy
            # recount this replaces cost tens of ms per wide wave.  With the
            # witness audit on, its int32[2] verdict rides the SAME fetch.
            wit = None
            if witness:
                k = min(self.witness_k, g.n)
                sample = jnp.asarray(
                    self._witness_rng.integers(0, g.n, size=k), jnp.int32)
                rows_cm, trav_np, wit = self._fetch_many(
                    value[: g.n], _plane_traversed(g, value, program),
                    _witness_check(g, value, sample, self.witness_budget,
                                   program.payload == "parents"))
            else:
                rows_cm, trav_np = self._fetch_pair(
                    value[: g.n], _plane_traversed(g, value, program))
            rows = rows_cm.T                             # [B, n]
            if check:
                self._guard_rows(rows, roots, lvl)
                if wit is not None and not int(wit[1]) and int(wit[0]):
                    raise IntegrityError(
                        f"witness audit failed: {int(wit[0])} sampled "
                        "(vertex, plane) discoveries have no "
                        + ("reached in-neighbor as parent"
                           if program.payload else
                           "in-neighbor at value - 1"))
        res = self._result(rows, b, lvl, inspected, push_iters,
                           pull_iters, dt, overflow_retries, budget,
                           trav_vec=trav_np)
        self.last_stats["discovery_popcounts"] = pcs
        self.last_stats["levels"] = levels
        if check:
            self.last_stats["integrity"] = dict(
                mode=self.integrity,
                sv_checks=len(pcs),
                witness_sampled=(0 if wit is None
                                 else min(self.witness_k, g.n)),
                witness_truncated=bool(wit is not None and int(wit[1])))
        return res

    def _result(self, rows, b, lvl, inspected, push_iters, pull_iters,
                dt, overflow_retries: int = 0, budget: int = 0,
                trav_vec: np.ndarray | None = None) -> VertexProgramResult:
        if trav_vec is None:
            traversed = count_traversed_edges(self._out_deg_np, rows)
        else:
            traversed = int(np.sum(trav_vec, dtype=np.int64))
        res = VertexProgramResult(
            levels=rows, batch=b, iterations=lvl, edges_inspected=inspected,
            push_iters=push_iters, pull_iters=pull_iters,
            traversed_edges=traversed, seconds=dt,
            host_transfers=self._transfers, algo=self.program.name,
            overflow_retries=overflow_retries, budget=budget)
        self.last_stats = dict(
            iterations=res.iterations, edges_inspected=res.edges_inspected,
            push_iters=res.push_iters, pull_iters=res.pull_iters,
            batch=res.batch, traversed_edges=res.traversed_edges,
            seconds=res.seconds, host_transfers=res.host_transfers,
            algo=res.algo, overflow_retries=res.overflow_retries,
            budget=res.budget)
        if trav_vec is not None:
            # per-plane counts let the serving layer account pad slots out
            # of TEPS without re-counting from the sliced level rows
            # (plain ints: last_stats must stay JSON-serializable)
            self.last_stats["traversed_per_plane"] = [
                int(x) for x in trav_vec]
        return res


# ---------------------------------------------------------------------------
# Instantiation 1: batched multi-source BFS (+ the legacy bool-plane
# baseline, kept as `MultiSourceBFSRunner(packed=False)` for differential
# tests and the throughput benchmark's "packed: off" arm).
# ---------------------------------------------------------------------------

def _p3_update_ms(cand_w, seen_w, use_pallas: bool):
    """Batched P3: fused per-plane Pallas kernel or plain jnp."""
    if use_pallas:
        from repro.kernels import ops as kops
        new_t, seen_t, _ = kops.fused_frontier_update_batch(
            cand_w.T, seen_w.T)       # planes-major for the kernel grid
        return new_t.T, seen_t.T
    new = cand_w & ~seen_w
    return new, seen_w | new


@partial(jax.jit, static_argnames=("budget", "use_pallas"))
def _boolplane_push_step(g: LocalGraph, frontier_w, seen_w, budget: int,
                         use_pallas: bool = False):
    """Bool-plane push: unpacks the whole frontier, builds a [budget, B]
    bool message array and a [n_pad+1, nb] bool scatter buffer per level."""
    nb = frontier_w.shape[1] * bitmap.WORD_BITS
    fmask = bitmap.unpack_rows(frontier_w)            # [n_pad, B']
    any_f = bitmap.any_rows(frontier_w)
    active, _ = compact_indices(any_f, g.n_pad)
    src, nbr, valid, total = expand_edges(active, g.out_indptr,
                                          g.out_indices, budget)
    msg = fmask[jnp.maximum(src, 0)] & valid[:, None]  # [budget, B']
    tgt = jnp.where(valid, nbr, g.n_pad)
    cand = jnp.zeros((g.n_pad + 1, nb), jnp.bool_)
    cand = cand.at[tgt].max(msg, mode="drop")[:-1]
    cand_w = bitmap.pack_rows(cand)
    new, seen2 = _p3_update_ms(cand_w, seen_w, use_pallas)
    return new, seen2, total, total > budget


@partial(jax.jit, static_argnames=("budget", "use_pallas"))
def _boolplane_pull_step(g: LocalGraph, frontier_w, seen_w, budget: int,
                         use_pallas: bool = False):
    """Bool-plane pull: vertices unseen by SOME source read their in-lists
    once and OR their parents' frontier masks (via bool plane arrays)."""
    nb = frontier_w.shape[1] * bitmap.WORD_BITS
    pmask = bitmap.plane_mask(nb)
    fmask = bitmap.unpack_rows(frontier_w)
    un_any = bitmap.any_rows(~seen_w & pmask)
    active, _ = compact_indices(un_any, g.n_pad)
    child, parent, valid, total = expand_edges(active, g.in_indptr,
                                               g.in_indices, budget)
    msg = fmask[jnp.maximum(parent, 0)] & valid[:, None]
    tgt = jnp.where(valid, child, g.n_pad)
    cand = jnp.zeros((g.n_pad + 1, nb), jnp.bool_)
    cand = cand.at[tgt].max(msg, mode="drop")[:-1]
    cand_w = bitmap.pack_rows(cand)
    new, seen2 = _p3_update_ms(cand_w, seen_w, use_pallas)
    return new, seen2, total, total > budget


@jax.jit
def _ms_iter_stats(g: LocalGraph, frontier_w, seen_w):
    nb = frontier_w.shape[1] * bitmap.WORD_BITS
    pmask = bitmap.plane_mask(nb)
    any_f = bitmap.any_rows(frontier_w)
    un_any = bitmap.any_rows(~seen_w & pmask)
    n_f = jnp.sum(any_f, dtype=jnp.int32)
    m_f = jnp.sum(jnp.where(any_f, g.out_deg, 0), dtype=jnp.int32)
    m_u = jnp.sum(jnp.where(un_any, g.in_deg, 0), dtype=jnp.int32)
    n_u = jnp.sum(un_any, dtype=jnp.int32)
    return n_f, m_f, m_u, n_u


class MultiSourceBFSRunner(VertexProgramRunner):
    """Batched hybrid MS-BFS: the BFS instantiation of the engine.

    ``packed=True`` (default) runs the shared packed-word pipeline.
    ``packed=False`` preserves the pre-packed bool-plane implementation as
    a differential/benchmark baseline (bool planes + per-scalar syncs).
    """

    program = BFS

    def __init__(self, g: LocalGraph, sched: SchedulerConfig | None = None,
                 init_budget: int = 1 << 15, use_pallas: bool = False,
                 packed: bool = True,
                 max_overflow_retries: int | None = None,
                 tile_rows: int | None = None, sparse_pull: bool = False,
                 integrity: str = "off", witness_k: int = 64,
                 witness_budget: int = 4096,
                 integrity_seed: int | None = 0):
        super().__init__(g, BFS, sched, init_budget, use_pallas,
                         max_overflow_retries, tile_rows, sparse_pull,
                         integrity, witness_k, witness_budget,
                         integrity_seed)
        self.packed = packed

    def run(self, roots, *, budget: int | None = None) -> VertexProgramResult:
        # NOTE: the bool-plane baseline performs no integrity checks — it
        # IS the reference the supervisor's differential audit compares
        # against, and the demotion ladder's last rung
        if self.packed:
            return super().run(roots, budget=budget)
        roots = validate_roots(np.asarray(roots), self.g.n).astype(np.int32)
        self._transfers = 0
        return self._run_boolplane(roots, budget)

    def _run_boolplane(self, roots: np.ndarray,
                       budget_override: int | None = None
                       ) -> VertexProgramResult:
        """Pre-packed-pipeline driver (bool planes + per-scalar syncs)."""
        g = self.g
        b = int(roots.size)
        frontier, seen, level = plane_seed_init(g, jnp.asarray(roots))
        mode = jnp.int32(PUSH)
        lvl = 0
        inspected = 0
        push_iters = pull_iters = 0
        overflow_retries = 0
        budget = budget_override or self.init_budget
        t0 = time.perf_counter()
        while True:
            n_f, m_f, m_u, n_u = _ms_iter_stats(g, frontier, seen)
            n_f, m_f, m_u, n_u = (self._fetch(n_f), self._fetch(m_f),
                                  self._fetch(m_u), self._fetch(n_u))
            if int(n_f) == 0:
                break
            mode = choose_mode(self.sched, mode, n_f, m_f, m_u, g.n, n_u)
            is_push = int(self._fetch(mode)) == PUSH  # another per-level sync
            step = (_boolplane_push_step if is_push
                    else _boolplane_pull_step)
            need = int(m_f) if is_push else int(m_u)
            while budget < min(need, g.out_indices.shape[0] + 1):
                budget *= 2
            seen0 = seen
            new, seen, total, overflow = step(g, frontier, seen0, budget,
                                              self.use_pallas)
            while bool(self._fetch(overflow)):
                overflow_retries += 1
                if (self.max_overflow_retries is not None
                        and overflow_retries > self.max_overflow_retries):
                    raise BudgetOverflowError(budget, int(need),
                                              overflow_retries)
                budget *= 2
                new, seen, total, overflow = step(g, frontier, seen0,
                                                  budget, self.use_pallas)
            new_mask = bitmap.unpack_rows(new, b)
            level = jnp.where(new_mask, lvl + 1, level)
            frontier = new
            lvl += 1
            inspected += int(self._fetch(total))
            if is_push:
                push_iters += 1
            else:
                pull_iters += 1
        level.block_until_ready()
        dt = time.perf_counter() - t0
        levels = self._fetch(level[: g.n]).T       # [B, n]
        return self._result(levels, b, lvl, inspected, push_iters,
                            pull_iters, dt, overflow_retries, budget)


# ---------------------------------------------------------------------------
# Instantiation 2: batched multi-seed connected components.
# ---------------------------------------------------------------------------

def component_labels(levels: np.ndarray, seeds: np.ndarray) -> np.ndarray:
    """Per-vertex CC labels from the multi-seed reach levels.

    ``label[v]`` = the smallest seed VERTEX ID whose component contains
    ``v`` (all seeds in one component reach the same vertex set at
    convergence, so labels are uniform per component), or -1 when no seed
    reaches ``v``."""
    levels = np.asarray(levels)
    seeds = np.asarray(seeds, np.int64)
    reach = levels < int(INF)                        # [B, n]
    big = np.iinfo(np.int64).max
    lab = np.where(reach, seeds[:, None], big).min(axis=0)
    return np.where(lab == big, -1, lab)


class ConnectedComponentsRunner(VertexProgramRunner):
    """Batched multi-seed CC: one plane per seed, flood fill to fixpoint.

    The engine must be built over the SYMMETRIZED graph (components are an
    undirected notion) — use :meth:`from_csr`, or pass a ``LocalGraph``
    built from ``repro.graph.symmetrize_csr`` output.  ``run(seeds)``
    returns hop levels from each seed ([B, n]; membership = ``level <
    INF``) plus ``result.labels`` — the classic per-vertex component
    labeling (min seed id, -1 for vertices no seed reaches).
    """

    program = CC

    @classmethod
    def from_csr(cls, csr, **kw) -> "ConnectedComponentsRunner":
        """Build from a (possibly directed) CSR: symmetrize, then wire up."""
        from repro.core.bfs_local import build_local_graph
        from repro.graph.csr import symmetrize_csr
        sym = symmetrize_csr(csr)
        # a symmetrized graph is its own transpose: one device copy
        return cls(build_local_graph(sym, sym), **kw)

    def _finalize(self, res: VertexProgramResult,
                  roots: np.ndarray) -> VertexProgramResult:
        res.labels = component_labels(res.levels, roots)
        self.last_stats["components"] = int(
            np.unique(res.labels[res.labels >= 0]).size)
        return res


# ---------------------------------------------------------------------------
# Instantiation 3: batched SSSP (unit-weight hop distances).
# ---------------------------------------------------------------------------

class SSSPRunner(VertexProgramRunner):
    """Batched single-source shortest paths, unit edge weights.

    One frontier plane per source; the apply is a min-plus relaxation
    (``dist = min(dist, lvl + 1)`` over newly-relaxed planes) rather than
    BFS's first-touch level write — with unit weights both converge to
    hop distances, which is what the differential tests pin against a
    dense Bellman–Ford oracle.  ``result.distances`` ([B, n], INF =
    unreachable) aliases the value rows.
    """

    program = SSSP


# ---------------------------------------------------------------------------
# Instantiation 4: batched BFS parent trees (Graph 500 kernel 2).
# ---------------------------------------------------------------------------

class BFSParentsRunner(VertexProgramRunner):
    """Batched BFS that answers each root with its BFS parent tree.

    One frontier plane per root, as BFS; the value plane carries parents
    (the ``parents`` payload): ``result.parents[b, root_b] == root_b``, -1
    where unreached, and every other reached vertex's parent is its
    smallest-id in-neighbour one level closer to the root, which makes the
    rows deterministic and exactly comparable with a plain oracle
    (``bfs_local.bfs_parent_oracle``).  The parent tree's depths are the
    BFS levels, so the Graph 500 validation rules hold by construction.
    """

    program = BFS_PARENTS
