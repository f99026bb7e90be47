"""The owner map of the budgeted edge expansion (``expand_edges``).

Each budget slot's owner, the active entry whose neighbour list holds the
slot, comes by one of two paths that ``owner_path`` picks from the static
shapes: the binary search (``search_owners``) or the merge
(``merge_owners``).  The two must be bit-identical, so every caller gets
the same ``(src, nbr, valid, total)`` whichever path its shapes pick:
over zero-degree active vertices, ``-1`` pads, budgets under, at and over
the edge total, budgets shorter and longer than the active list, on both
sides of the crossover, and under ``jax.vmap`` as the sharded engine calls
it.  A batched run whose push rungs straddle the crossover records each
level's path, and answers as a run held to the search does.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.bfs_local as bl
from repro.core import MultiSourceBFSRunner, build_local_graph, \
    msbfs_reference
from repro.graph import csr_from_edges, rmat_edges, symmetrize_csr

N = 96


@pytest.fixture(scope="module")
def csr():
    """A random digraph on ``N`` vertices with a third of them arc-less."""
    rng = np.random.default_rng(7)
    src = rng.integers(0, 2 * N // 3, 700)
    dst = rng.integers(0, N, 700)
    return csr_from_edges(src, dst, N)


def _active(csr, kind: str) -> np.ndarray:
    """An active list as ``compact_indices`` leaves it: ids, then -1."""
    deg = np.diff(csr.indptr)
    rng = np.random.default_rng(len(kind))
    if kind == "zero_degree":          # every third active has no arcs
        ids = np.sort(np.concatenate([
            rng.choice(np.flatnonzero(deg == 0), 8, replace=False),
            rng.choice(np.flatnonzero(deg > 0), 16, replace=False)]))
        return np.concatenate([ids, np.full(N - ids.size, -1)])
    if kind == "all_pads":
        return np.full(N, -1)
    if kind == "no_pads":
        return np.arange(N)
    ids = np.sort(rng.choice(N, 40, replace=False))
    return np.concatenate([ids, np.full(N - ids.size, -1)])


def _total(csr, active) -> int:
    deg = np.diff(csr.indptr)
    return int(deg[active[active >= 0]].sum())


def _expected(csr, active, budget):
    """The flattened neighbour lists, by numpy: (src, nbr) per arc."""
    ids = active[active >= 0]
    src = np.repeat(ids, np.diff(csr.indptr)[ids])
    nbr = np.concatenate([csr.indices[csr.indptr[v]:csr.indptr[v + 1]]
                          for v in ids] + [np.zeros(0, np.int64)])
    return src[:budget], nbr[:budget]


def _hold(monkeypatch, path: str):
    """Hold every shape to one owner path by the crossover constant."""
    monkeypatch.setattr(bl, "OWNER_MERGE_RATIO",
                        0 if path == "search" else 1 << 40)


def _expand(active, csr, budget, path=None, monkeypatch=None):
    """``expand_edges`` on the host arrays; ``path`` holds the owner map
    to one path by the crossover constant, traced afresh."""
    if path is not None:
        _hold(monkeypatch, path)
        assert bl.owner_path(len(active), budget) == path
    try:
        out = jax.jit(lambda a, ip, ix: bl.expand_edges(a, ip, ix, budget))(
            jnp.asarray(active, jnp.int32),
            jnp.asarray(csr.indptr, jnp.int32),
            jnp.asarray(csr.indices, jnp.int32))
    finally:
        if monkeypatch is not None:
            monkeypatch.undo()
    return [np.asarray(x) for x in out]


def _check(csr, active, budget, got):
    src, nbr, valid, total = got
    assert int(total) == _total(csr, active)
    k = min(int(total), budget)
    np.testing.assert_array_equal(valid, np.arange(budget) < int(total))
    want_src, want_nbr = _expected(csr, active, budget)
    np.testing.assert_array_equal(src[:k], want_src)
    np.testing.assert_array_equal(nbr[:k], want_nbr)
    assert (src[k:] == -1).all() and (nbr[k:] == -1).all()


def _budget(csr, active, rel: str) -> int:
    t = _total(csr, active)
    return {"under": max(1, t - 5), "equal": max(1, t), "over": t + 37,
            "short": N // 4, "long": 4 * N}[rel]


CASES = [("zero_degree", "over"), ("all_pads", "over"),
         ("no_pads", "long"), ("mixed", "under"), ("mixed", "equal"),
         ("mixed", "over"), ("mixed", "short"), ("mixed", "long")]


@pytest.mark.parametrize("path", ["merge", "search"])
@pytest.mark.parametrize("kind,rel", CASES)
def test_each_path_expands_as_the_search(csr, monkeypatch, kind, rel, path):
    active = _active(csr, kind)
    budget = _budget(csr, active, rel)
    if rel == "short":
        assert budget < len(active)
    if rel == "long":
        assert budget > len(active)
    got = _expand(active, csr, budget, path, monkeypatch)
    for a, b in zip(got, _expand(active, csr, budget, "search",
                                 monkeypatch)):
        np.testing.assert_array_equal(a, b)
    _check(csr, active, budget, got)


@pytest.mark.parametrize("side", ["merge", "search"])
def test_shapes_pick_the_path_on_each_side_of_the_crossover(csr, side):
    """No patch: a budget just at the crossover merges, one just below
    searches, and both expand exactly."""
    active = _active(csr, "mixed")
    edge = -(-len(active) // bl.OWNER_MERGE_RATIO)   # least merging budget
    budget = edge if side == "merge" else edge - 1
    assert budget >= 1, "the crossover lies below one slot at this size"
    assert bl.owner_path(len(active), budget) == side
    _check(csr, active, budget, _expand(active, csr, budget))


@pytest.mark.parametrize("budget", [1, 7, 64, 65, 300])
def test_merge_owners_equal_search_owners_on_every_slot(budget):
    """Bit-identical on every slot, the slots past the total included:
    repeated cumulative degrees (zero-degree entries) and a tail of pads
    whose sums pass the budget."""
    deg = np.random.default_rng(budget).integers(0, 4, 50)
    deg[[0, 10, 11, 12]] = 0
    cum = jnp.asarray(np.cumsum(deg), jnp.int32)
    np.testing.assert_array_equal(bl.merge_owners(cum, budget),
                                  bl.search_owners(cum, budget))


@pytest.mark.parametrize("path", ["merge", "search"])
def test_paths_agree_under_vmap(csr, monkeypatch, path):
    """Per-shard expansion as ``DistributedBFS`` runs it: ``expand_edges``
    vmapped over stacked active lists and graphs."""
    actives = np.stack([_active(csr, "mixed"), _active(csr, "zero_degree")])
    budget = 200

    def run(p):
        _hold(monkeypatch, p)
        try:
            ip = jnp.tile(jnp.asarray(csr.indptr, jnp.int32), (2, 1))
            ix = jnp.tile(jnp.asarray(csr.indices, jnp.int32), (2, 1))
            return [np.asarray(x) for x in jax.jit(jax.vmap(
                lambda a, i, j: bl.expand_edges(a, i, j, budget)))(
                    jnp.asarray(actives, jnp.int32), ip, ix)]
        finally:
            monkeypatch.undo()

    got, want = run(path), run("search")
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    for s in range(2):
        _check(csr, actives[s], budget, [x[s] for x in got])


def test_straddling_run_records_each_level_path(monkeypatch):
    """Push rungs from one slot up to the wide level: the narrow ones
    search and the wide ones merge, each record names the path its
    shapes pick, and the rows equal a run held to the search."""
    src, dst = rmat_edges(12, 16, seed=3)
    csr = symmetrize_csr(csr_from_edges(src, dst, 1 << 12))
    g = build_local_graph(csr, csr)
    deg = np.diff(csr.indptr)
    roots = np.flatnonzero(deg == 1)[:2]          # a first push of need 2
    assert bl.owner_path(g.n_pad, 2) == "search"
    eng = MultiSourceBFSRunner(g, init_budget=1)
    rows = eng.run_batch(roots)
    push = [r for r in eng.last_stats["levels"] if r["mode"] == "push"]
    for r in push:
        assert r["owner"] == bl.owner_path(g.n_pad, r["budget"]), r
    assert {r["owner"] for r in push} == {"merge", "search"}
    np.testing.assert_array_equal(rows, msbfs_reference(g, roots))
    _hold(monkeypatch, "search")
    jax.clear_caches()                  # the steps trace again, searching
    try:
        held = MultiSourceBFSRunner(g, init_budget=1)
        held_rows = held.run_batch(roots)
        assert {r["owner"] for r in held.last_stats["levels"]
                if r["mode"] == "push"} == {"search"}
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    np.testing.assert_array_equal(rows, held_rows)
