"""BFS parent trees (Graph 500 kernel 2) through the vertex-program engine.

The ``bfs_parents`` program carries a parent plane beside BFS's bit
planes: push scatter-mins each budgeted arc's source id, the dense pull
reads the first hit of each in-list off its OR-scan.  Each answered row
must equal the plain deque oracle (``bfs_local.bfs_parent_oracle``:
smallest-id in-neighbour one level up) exactly, and pass Graph 500's five
validation rules, on every schedule, batch width and serving path; BFS,
CC and SSSP must keep the steps they had.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BFS, BFS_PARENTS, CC, SSSP, BFSParentsRunner,
                        ConnectedComponentsRunner, IntegrityError,
                        MultiSourceBFSRunner, SchedulerConfig, SSSPRunner,
                        bfs_oracle, bfs_parent_oracle, build_local_graph,
                        check_parent_rows, msbfs_reference, vp_reference)
from repro.core import vertex_program as vp
from repro.core.bfs_local import INF
from repro.ft import EngineSupervisor, IntegrityConfig, check_rows
from repro.graph import (CSRGraph, csr_from_edges, symmetrize_csr,
                         transpose_csr, uniform_edges)
from repro.graph.generators import rmat_edges


def _kron(scale: int, seed: int = 1):
    src, dst = rmat_edges(scale, 16, seed=seed)
    return symmetrize_csr(csr_from_edges(src, dst, 1 << scale))


def _urand(scale: int, seed: int = 2):
    n = 1 << scale
    src, dst = uniform_edges(n, 16 * n, seed=seed)
    return symmetrize_csr(csr_from_edges(src, dst, n))


def _directed(scale: int, seed: int = 3):
    n = 1 << scale
    src, dst = uniform_edges(n, 6 * n, seed=seed)
    return csr_from_edges(src, dst, n)


def _islands(seed: int = 4):
    """Two random components over the first 400 vertices, the last 112
    vertices isolated."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 200, (2, 1200))
    b = rng.integers(200, 400, (2, 1200))
    src, dst = np.concatenate([a[0], b[0]]), np.concatenate([a[1], b[1]])
    return symmetrize_csr(csr_from_edges(src, dst, 512))


GRAPHS = {"kron8": lambda: _kron(8), "kron10": lambda: _kron(10),
          "urand9": lambda: _urand(9), "directed9": lambda: _directed(9),
          "islands": _islands}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def graph(request):
    csr = GRAPHS[request.param]()
    return csr, build_local_graph(csr, transpose_csr(csr))


def _roots(csr, b: int, seed: int = 0) -> np.ndarray:
    """``b`` roots, the first of them without arcs where the graph has
    such a vertex (an isolated root answers with itself alone)."""
    deg = np.diff(csr.indptr)
    rng = np.random.default_rng(seed)
    roots = rng.choice(np.flatnonzero(deg > 0), b)
    isolated = np.flatnonzero((deg == 0) & (np.diff(
        transpose_csr(csr).indptr) == 0))
    if isolated.size:
        roots[0] = isolated[0]
    return roots.astype(np.int64)


def _oracle(csr, roots) -> np.ndarray:
    return np.stack([bfs_parent_oracle(csr, int(r)) for r in roots])


def graph500_faults(csr, root: int, parent) -> list[str]:
    """Graph 500's five validation rules, on the tree's own depths: the
    names of those the row breaks (rule 3 read for arcs, so it holds on
    a directed graph too)."""
    parent = np.asarray(parent, np.int64)
    n = csr.num_vertices
    faults = []
    depth = np.full(n, -1, np.int64)
    depth[root] = 0
    # (1) no cycle: every chain of parents reaches the root within n steps
    for v in np.flatnonzero(parent >= 0):
        chain, u = [], int(v)
        while depth[u] < 0 and len(chain) <= n:
            chain.append(u)
            u = int(parent[u])
        if depth[u] < 0:
            faults.append("1:cycle")
            break
        for k, w in enumerate(reversed(chain)):
            depth[w] = depth[u] + k + 1
    src = np.repeat(np.arange(n), np.diff(csr.indptr))
    dst = np.asarray(csr.indices, np.int64)
    tree = np.flatnonzero(parent >= 0)
    tree = tree[tree != root]
    # (2) tree edges join depths that differ by one
    if np.any(depth[tree] != depth[parent[tree]] + 1):
        faults.append("2:tree_edge_depth")
    # (3) an arc out of a reached vertex reaches no deeper than one more
    out = depth[src] >= 0
    if np.any((depth[dst[out]] < 0)
              | (depth[dst[out]] > depth[src[out]] + 1)):
        faults.append("3:graph_edge_depth")
    # (4) the tree spans exactly what the root reaches
    if not np.array_equal(parent >= 0, bfs_oracle(csr, root) < INF):
        faults.append("4:span")
    # (5) each parent is joined to its vertex by an arc
    arcs = set(zip(src.tolist(), dst.tolist()))
    if any((int(parent[v]), int(v)) not in arcs for v in tree):
        faults.append("5:not_an_edge")
    return faults


def _check(csr, roots, rows):
    rows = np.asarray(rows)
    np.testing.assert_array_equal(rows, _oracle(csr, roots))
    for r, row in zip(roots, rows):
        assert graph500_faults(csr, int(r), row) == []


# -- the engine against the oracle --------------------------------------------

@pytest.mark.parametrize("policy", ["push", "pull", "beamer"])
def test_rows_equal_the_oracle(graph, policy):
    csr, g = graph
    roots = _roots(csr, 32)
    eng = BFSParentsRunner(g, sched=SchedulerConfig(policy=policy))
    res = eng.run(roots)
    _check(csr, roots, res.parents)
    modes = {r["mode"] for r in eng.last_stats["levels"]}
    if policy != "beamer":
        assert modes == {policy}
    # teps counts what each tree reaches, as BFS counts its levels
    assert res.traversed_edges == MultiSourceBFSRunner(g).run(
        roots).traversed_edges
    assert res.host_transfers == res.iterations + 2


def test_schedule_mixes_push_and_pull():
    csr = _kron(10)
    g = build_local_graph(csr, csr)
    roots = _roots(csr, 32, seed=5)
    eng = BFSParentsRunner(g, init_budget=1 << 8)
    rows = eng.run_batch(roots)
    modes = [r["mode"] for r in eng.last_stats["levels"]]
    assert modes[0] == "push" and "pull" in modes
    _check(csr, roots, rows)


@pytest.mark.parametrize("b", [1, 5, 32, 33])
def test_batch_widths_and_pad_planes(b):
    csr = _urand(9, seed=6)
    g = build_local_graph(csr, csr)
    roots = _roots(csr, b, seed=b)
    _check(csr, roots, BFSParentsRunner(g).run_batch(roots))


def test_first_hits_span_several_chunks(monkeypatch):
    """A dense pull level writes its first-hit arcs in several passes of
    the device loop (chunk size patched down; an odd size, so the last
    pass starts clamped), and the rows do not change."""
    csr = _kron(9, seed=11)
    g = build_local_graph(csr, csr)
    roots = _roots(csr, 32, seed=7)
    monkeypatch.setattr(vp, "PARENT_CHUNK", 37)
    jax.clear_caches()
    try:
        eng = BFSParentsRunner(g, sched=SchedulerConfig(policy="pull"))
        rows = eng.run_batch(roots)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    hits = [r["hits"] for r in eng.last_stats["levels"]]
    assert max(hits) > 3 * 37
    _check(csr, roots, rows)


def test_level_records_count_the_payload_rows():
    csr = _kron(10)
    g = build_local_graph(csr, csr)
    roots = _roots(csr, 32, seed=3)
    eng = BFSParentsRunner(g, init_budget=1 << 8)
    res = eng.run(roots)
    recs = eng.last_stats["levels"]
    for r in recs:
        assert set(r) == {"mode", "budget", "need", "total", "retries",
                          "hits"} | ({"owner"} if r["mode"] == "push"
                                     else set())
        if r["mode"] == "push":      # edges that reach a new pair
            assert r["hits"] <= min(r["total"], r["budget"])
    # a dense pull writes one arc per new (vertex, plane) at most, and at
    # least one per vertex that any plane reaches at that level
    pcs = eng.last_stats["discovery_popcounts"]
    for lvl, r in enumerate(recs):
        if r["mode"] == "pull":
            assert 0 < r["hits"] <= pcs[lvl + 1]
    assert res.iterations == len(recs)


def test_unsorted_in_lists_are_sorted_on_build():
    """First hit equals smallest id only on sorted in-lists: a CSC whose
    lists run backwards is sorted by ``build_local_graph``."""
    csr = _directed(8, seed=9)
    csc = transpose_csr(csr)
    rev = np.concatenate([csc.indices[a:b][::-1] for a, b in
                          zip(csc.indptr[:-1], csc.indptr[1:])])
    assert not np.array_equal(rev, csc.indices)
    g = build_local_graph(csr, CSRGraph(csc.num_vertices, csc.indptr, rev))
    np.testing.assert_array_equal(np.asarray(g.in_indices), csc.indices)
    roots = _roots(csr, 8)
    eng = BFSParentsRunner(g, sched=SchedulerConfig(policy="pull"))
    _check(csr, roots, eng.run_batch(roots))


@pytest.mark.parametrize("path", ["sparse_pull", "pallas"])
def test_budgeted_pulls_find_the_same_parents(path):
    csr = _kron(8, seed=4)
    g = build_local_graph(csr, csr)
    roots = _roots(csr, 32, seed=2)
    eng = BFSParentsRunner(g, sched=SchedulerConfig(policy="pull"),
                           **{"sparse_pull" if path == "sparse_pull"
                              else "use_pallas": True})
    _check(csr, roots, eng.run_batch(roots))


def test_vp_reference_gives_parent_rows():
    csr = _urand(8, seed=8)
    g = build_local_graph(csr, csr)
    roots = _roots(csr, 8)
    np.testing.assert_array_equal(
        np.asarray(vp_reference(g, roots, BFS_PARENTS)), _oracle(csr, roots))


# -- serving ------------------------------------------------------------------

def test_rows_served_through_the_batcher():
    from repro.launch.dynbatch import DynamicBatcher
    from repro.launch.serve import build_engine
    csr = _kron(9, seed=12)
    ds = type("Ds", (), {"csr": csr, "csc": csr})()
    engine, deg = build_engine(ds, algo="bfs_parents", distributed=False)
    assert isinstance(engine, BFSParentsRunner)
    b = DynamicBatcher(engine, window=10.0, clock=lambda: 0.0)
    roots = _roots(csr, 11, seed=13)
    futs = [b.submit(int(r), block=False) for r in roots]
    waves = b.flush()
    b.close()
    assert len(waves) == 1
    rows = [f.result(timeout=0) for f in futs]
    _check(csr, roots, rows)
    # the wave's teps counts the 11 real trees, not the pad planes
    assert waves[0].traversed_edges == int(sum(
        deg[np.asarray(r) >= 0].sum() for r in rows))


def test_serve_cli_path_returns_json_stats():
    from repro.launch.serve import serve_bfs_async
    out = serve_bfs_async("tiny-16-4", requests=6, window=0.01, max_batch=8,
                          algo="bfs_parents")
    assert out["algo"] == "bfs_parents" and out["requests"] == 6
    json.dumps(out)


def test_sharded_parents_are_refused():
    from repro.launch.serve import build_engine
    with pytest.raises(ValueError, match="R2"):
        build_engine("tiny-16-4", algo="bfs_parents", distributed=True)


# -- guards -------------------------------------------------------------------

def test_parent_row_check():
    roots = np.asarray([0, 2])
    rows = np.asarray([[0, 0, 1, -1], [1, 2, 2, -1]], np.int32)
    check_parent_rows(rows, roots)
    check_rows(rows, roots, 1, BFS_PARENTS)
    for v, bad in [(3, 4), (3, -2), (0, 1)]:
        broken = rows.copy()
        broken[0, v] = bad
        with pytest.raises(IntegrityError):
            check_parent_rows(broken, roots)
    with pytest.raises(IntegrityError):     # parent rows are not levels
        check_rows(rows, roots, 1, BFS)


def test_witness_check_on_parent_rows():
    csr = _urand(8, seed=14)
    g = build_local_graph(csr, csr)
    roots = _roots(csr, 4, seed=1)
    value = jnp.asarray(BFSParentsRunner(g).run(roots).parents.T)
    sample = jnp.arange(csr.num_vertices, dtype=jnp.int32)
    viol, trunc = (int(x) for x in vp._witness_check(
        g, value, sample, 1 << 14, parents=True))
    assert viol == 0 and trunc == 0
    v = int(np.flatnonzero(np.asarray(value[:, 1]) >= 0)[-1])
    non_nbr = int(np.setdiff1d(np.arange(csr.num_vertices),
                               csr.neighbors(v))[0])
    bad = value.at[v, 1].set(non_nbr)       # a parent that is no neighbour
    viol, _ = (int(x) for x in vp._witness_check(
        g, bad, sample, 1 << 14, parents=True))
    assert viol >= 1


@pytest.mark.parametrize("mode", ["witness", "audit"])
def test_supervised_waves_pass_their_integrity_checks(mode):
    """The supervisor checks parent rows as parent rows, and its
    differential audit re-runs the wave off the Pallas rung and compares
    the rows exactly."""
    csr = _kron(8, seed=15)
    g = build_local_graph(csr, csr)
    runner = BFSParentsRunner(g, use_pallas=True)
    sup = EngineSupervisor(runner, watchdog=False, backoff=0.0,
                           integrity=IntegrityConfig(mode=mode,
                                                     audit_rate=1.0))
    roots = _roots(csr, 32, seed=16)
    wave = sup.run_wave(roots)
    assert wave.n_failed == 0
    _check(csr, roots, wave.levels())
    st = sup.stats()["integrity"]
    assert st["violations"] == 0
    assert st["audits"] == (mode == "audit") and st["audit_failures"] == 0


# -- the other programs keep their steps --------------------------------------

@pytest.mark.parametrize("program,runner", [
    (BFS, MultiSourceBFSRunner), (CC, ConnectedComponentsRunner),
    (SSSP, SSSPRunner)], ids=["bfs", "cc", "sssp"])
def test_level_programs_keep_their_steps_and_rows(program, runner):
    csr = _kron(8, seed=17)
    g = build_local_graph(csr, csr)
    roots = _roots(csr, 32, seed=18)
    eng = runner(g)
    np.testing.assert_array_equal(eng.run_batch(roots),
                                  msbfs_reference(g, roots))
    assert all("hits" not in r for r in eng.last_stats["levels"])
    frontier, seen, value, _ = vp.vp_init_state(g, jnp.asarray(roots),
                                                program)
    args = (g, frontier, seen, value, np.int32(0))
    for step, budget in ((vp.vp_push_step, 1 << 10), (vp.vp_pull_step, 0)):
        text = step.lower(*args, program, budget).as_text()
        assert str(vp.NO_PARENT) not in text       # no parent payload
        assert "tensor<7xi32>" in text             # the statvec as it was
    text = vp.vp_pull_step_parents.lower(
        g, frontier, seen, value, np.int32(0), BFS_PARENTS, 0).as_text()
    assert str(vp.NO_PARENT) in text and "tensor<8xi32>" in text
