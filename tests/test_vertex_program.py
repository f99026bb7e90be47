"""Differential tests for the generic vertex-program engine.

The tentpole contract: the (init, apply/commit, combine, done) bundle
drives ONE shared packed-plane pipeline, and every instantiation —
BFS (covered in test_msbfs_differential), CC and SSSP here — must agree
bit-for-bit with an independent dense numpy oracle (union-find component
labels for CC, Bellman–Ford relaxation for SSSP) at batch widths that
exercise partial plane words (1, 32, 48), with and without the Pallas
propagate kernel, on graphs with isolated vertices and self-loops.

Also pinned: the inherited one-sync-per-level protocol
(``host_transfers == iterations + 2``) and shared root validation, the
``vp_reference`` dense loop, the serve/dynbatch integration of the
``--algo`` paths, and the program-parameterized distributed engine.
"""
import json

import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import (CC, SSSP, ConnectedComponentsRunner,
                        MultiSourceBFSRunner, SSSPRunner, VertexProgram,
                        bfs_oracle, build_local_graph, component_labels,
                        get_program, partition_graph, vp_reference)
from repro.core.bfs_distributed import DistributedBFS
from repro.graph import csr_from_edges, symmetrize_csr, transpose_csr

N = 128
INF = 1 << 30


def _awkward_graph(n: int, m: int, seed: int):
    """Random digraph with guaranteed isolated vertices and self-loops
    (same construction as the MS-BFS differential sweep)."""
    rng = np.random.default_rng(seed)
    hi = (3 * n) // 4
    src = rng.integers(0, hi, m)
    dst = rng.integers(0, hi, m)
    loops = np.arange(0, hi, 16)
    src = np.concatenate([src, loops])
    dst = np.concatenate([dst, loops])
    csr = csr_from_edges(src, dst, n)
    assert (np.diff(csr.indptr)[hi:] == 0).all()      # isolates exist
    return csr


def _roots(n: int, batch: int, seed: int) -> np.ndarray:
    """Roots including an isolated vertex and a self-loop vertex."""
    rng = np.random.default_rng(seed)
    roots = rng.choice(n, batch, replace=False)
    if batch >= 2:
        roots[0] = n - 1        # isolated (edges confined to [0, 3n/4))
        roots[1] = 16           # self-loop vertex
    return roots.astype(np.int32)


# ---------------------------------------------------------------------------
# independent numpy oracles
# ---------------------------------------------------------------------------

def _bellman_ford_oracle(csr, root: int) -> np.ndarray:
    """Dense unit-weight Bellman–Ford: relax every edge until fixpoint."""
    n = csr.indptr.size - 1
    src = np.repeat(np.arange(n), np.diff(csr.indptr))
    dst = np.asarray(csr.indices)
    dist = np.full(n, INF, np.int64)
    dist[root] = 0
    for _ in range(n):
        nd = dist.copy()
        np.minimum.at(nd, dst, np.minimum(dist[src] + 1, INF))
        if (nd == dist).all():
            break
        dist = nd
    return dist


def _cc_oracle_labels(csr, seeds: np.ndarray) -> np.ndarray:
    """Union-find over the undirected edge set; label[v] = min seed id in
    v's component, -1 when no seed lands in it."""
    n = csr.indptr.size - 1
    src = np.repeat(np.arange(n), np.diff(csr.indptr))
    dst = np.asarray(csr.indices)
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for u, v in zip(src.tolist(), dst.tolist()):
        ru, rv = find(u), find(v)
        if ru != rv:
            parent[ru] = rv
    comp = np.asarray([find(v) for v in range(n)])
    labels = np.full(n, -1, np.int64)
    for s in sorted((int(s) for s in seeds), reverse=True):
        labels[comp == comp[s]] = s
    return labels


# ---------------------------------------------------------------------------
# CC differential: runner vs union-find oracle vs per-seed BFS oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp-p3", "pallas-p3"])
@pytest.mark.parametrize("batch", [1, 32, 48])
def test_cc_runner_vs_oracles(batch, use_pallas):
    csr = _awkward_graph(N, 512, seed=300 + batch)
    seeds = _roots(N, batch, seed=batch + 5)
    res = ConnectedComponentsRunner.from_csr(
        csr, use_pallas=use_pallas).run(seeds)
    assert res.algo == "cc" and res.levels.shape == (batch, N)
    np.testing.assert_array_equal(res.labels, _cc_oracle_labels(csr, seeds))
    # per-seed reach levels are BFS levels on the symmetrized graph
    sym = symmetrize_csr(csr)
    for i, s in enumerate(seeds):
        np.testing.assert_array_equal(res.levels[i].astype(np.int64),
                                      bfs_oracle(sym, int(s)))


def test_cc_labels_uniform_and_component_count():
    csr = _awkward_graph(N, 512, seed=17)
    seeds = _roots(N, 32, seed=2)
    runner = ConnectedComponentsRunner.from_csr(csr)
    res = runner.run(seeds)
    # all seeds in one component agree on the min-seed label; every seed
    # labels at least itself
    for i, s in enumerate(seeds):
        assert res.labels[s] >= 0 and res.labels[s] <= s
    n_components = int(np.unique(res.labels[res.labels >= 0]).size)
    assert runner.last_stats["components"] == n_components >= 1
    # an isolated seed is its own component
    assert res.labels[N - 1] == N - 1


def test_component_labels_min_seed_semantics():
    levels = np.asarray([[0, 1, INF, INF],      # seed 3 reaches {0, 1}
                         [1, 0, INF, INF],      # seed 1 reaches {0, 1}
                         [INF, INF, 0, INF]])   # seed 2 reaches {2}
    labels = component_labels(levels, np.asarray([3, 1, 2]))
    np.testing.assert_array_equal(labels, [1, 1, 2, -1])


# ---------------------------------------------------------------------------
# SSSP differential: runner vs dense Bellman–Ford, and vs BFS (unit
# weights make them coincide — on the SAME directed graph)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp-p3", "pallas-p3"])
@pytest.mark.parametrize("batch", [1, 32, 48])
def test_sssp_runner_vs_bellman_ford(batch, use_pallas):
    csr = _awkward_graph(N, 512, seed=400 + batch)
    g = build_local_graph(csr, transpose_csr(csr))
    roots = _roots(N, batch, seed=3 * batch + 2)
    res = SSSPRunner(g, use_pallas=use_pallas).run(roots)
    assert res.algo == "sssp"
    assert res.distances is res.levels          # SSSP alias
    for i, r in enumerate(roots):
        np.testing.assert_array_equal(res.distances[i].astype(np.int64),
                                      _bellman_ford_oracle(csr, int(r)))


def test_sssp_equals_bfs_on_unit_weights():
    csr = _awkward_graph(N, 512, seed=8)
    g = build_local_graph(csr, transpose_csr(csr))
    roots = _roots(N, 33, seed=4)               # crosses a plane word
    sssp = SSSPRunner(g).run(roots)
    bfs = MultiSourceBFSRunner(g).run(roots)
    np.testing.assert_array_equal(sssp.distances, bfs.levels)


# ---------------------------------------------------------------------------
# inherited engine contracts: one-sync-per-level + shared root validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda g, csr: ConnectedComponentsRunner.from_csr(csr),
    lambda g, csr: SSSPRunner(g),
], ids=["cc", "sssp"])
def test_one_host_transfer_per_level_inherited(make):
    csr = _awkward_graph(N, 512, seed=9)
    g = build_local_graph(csr, transpose_csr(csr))
    res = make(g, csr).run(_roots(N, 32, seed=3))
    assert res.iterations > 1
    assert res.host_transfers == res.iterations + 2


@pytest.mark.parametrize("make", [
    lambda g, csr: ConnectedComponentsRunner.from_csr(csr),
    lambda g, csr: SSSPRunner(g),
], ids=["cc", "sssp"])
def test_root_validation_inherited(make):
    csr = _awkward_graph(N, 256, seed=1)
    g = build_local_graph(csr, transpose_csr(csr))
    runner = make(g, csr)
    with pytest.raises(ValueError):
        runner.run(np.asarray([0, N], np.int32))
    with pytest.raises(ValueError):
        runner.run(np.asarray([2 ** 32 + 5], np.int64))   # must not wrap
    with pytest.raises(ValueError, match="integers"):
        runner.run(np.asarray([5.7]))                     # must not truncate


# ---------------------------------------------------------------------------
# vp_reference: the dense jit loop must agree per program
# ---------------------------------------------------------------------------

def test_vp_reference_parity():
    csr = _awkward_graph(N, 512, seed=23)
    roots = _roots(N, 31, seed=6)
    g = build_local_graph(csr, transpose_csr(csr))
    np.testing.assert_array_equal(np.asarray(vp_reference(g, roots, SSSP)),
                                  SSSPRunner(g).run(roots).distances)
    sym = symmetrize_csr(csr)
    g_sym = build_local_graph(sym, transpose_csr(sym))
    np.testing.assert_array_equal(
        np.asarray(vp_reference(g_sym, roots, CC)),
        ConnectedComponentsRunner(g_sym).run(roots).levels)


def test_get_program_registry():
    assert get_program("cc") is CC and get_program("sssp") is SSSP
    assert get_program("bfs").name == "bfs"
    with pytest.raises(ValueError, match="unknown vertex program"):
        get_program("pagerank")


# ---------------------------------------------------------------------------
# serving integration: build_engine / bfs_batch / dynbatch over --algo
# ---------------------------------------------------------------------------

def test_build_engine_serves_cc_and_sssp_locally():
    from repro.graph import get_dataset
    from repro.launch.serve import bfs_batch, build_engine
    csr = get_dataset("tiny-16-4").csr
    roots = [0, 5, 9]

    engine, deg = build_engine("tiny-16-4", algo="sssp", distributed=False)
    out = bfs_batch(roots, engine=engine, out_deg=deg)
    assert out["algo"] == "sssp" and out["batch"] == 3
    for i, r in enumerate(roots):
        np.testing.assert_array_equal(
            np.asarray(out["levels"][i], np.int64),
            _bellman_ford_oracle(csr, r))

    engine, deg = build_engine("tiny-16-4", algo="cc", distributed=False)
    out = bfs_batch(roots, engine=engine, out_deg=deg)
    assert out["algo"] == "cc" and out["components"] >= 1
    sym = symmetrize_csr(csr)
    for i, r in enumerate(roots):
        np.testing.assert_array_equal(
            np.asarray(out["levels"][i], np.int64), bfs_oracle(sym, r))
    # stats (levels popped) must be JSON-serializable for the serve CLI
    out.pop("levels")
    json.dumps(out)


def test_serve_bfs_async_algo_paths_return_json_stats():
    from repro.launch.serve import serve_bfs_async
    for algo in ("cc", "sssp"):
        out = serve_bfs_async("tiny-16-4", requests=6, window=0.01,
                              max_batch=8, algo=algo)
        assert out["algo"] == algo and out["requests"] == 6
        assert out["waves"] >= 1
        json.dumps(out)


def test_dynbatcher_discovers_out_deg_via_protocol():
    """Satellite: no ``out_deg=`` kwarg and no ``.g`` sniffing — the
    batcher reads the engine protocol's ``out_deg`` property, so TEPS
    stats survive for CC/SSSP engines too."""
    from repro.launch.dynbatch import DynamicBatcher
    from repro.launch.serve import build_engine
    engine, deg = build_engine("tiny-16-4", algo="cc", distributed=False)
    b = DynamicBatcher(engine, window=10.0, clock=lambda: 0.0)
    np.testing.assert_array_equal(b.out_deg, deg)
    for r in (0, 3, 7):
        b.submit(r, block=False)
    waves = b.flush()
    assert len(waves) == 1 and waves[0].traversed_edges > 0
    assert "aggregate_teps" in b.stats()
    b.close()


# ---------------------------------------------------------------------------
# distributed engine carrying a program
# ---------------------------------------------------------------------------

def _dist_engine(program, seed: int = 3, symmetric: bool = False):
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, 64, 256), rng.integers(0, 64, 256)
    csr = csr_from_edges(src, dst, 64)
    if symmetric:
        csr = symmetrize_csr(csr)
    pg = partition_graph(csr, transpose_csr(csr), 4)
    mesh = make_mesh((1,), ("data",))
    return csr, DistributedBFS(pg, mesh, program=program)


def test_distributed_sssp_vs_bellman_ford():
    csr, eng = _dist_engine(SSSP)
    roots = np.asarray([0, 2, 31, 63])
    dists = eng.run_batch(roots)
    assert eng.last_stats["algo"] == "sssp"
    for i, r in enumerate(roots):
        np.testing.assert_array_equal(dists[i],
                                      _bellman_ford_oracle(csr, int(r)))


def test_distributed_cc_vs_bfs_oracle():
    csr, eng = _dist_engine(CC, symmetric=True)
    seeds = np.asarray([0, 5, 40, 63])
    levels = eng.run_batch(seeds)
    for i, s in enumerate(seeds):
        np.testing.assert_array_equal(levels[i], bfs_oracle(csr, int(s)))
    labels = component_labels(levels, seeds)
    np.testing.assert_array_equal(labels, _cc_oracle_labels(csr, seeds))


def test_distributed_rejects_non_or_combine():
    """The distributed crossbar is an OR-reduce-scatter; a payload-plane
    combine must fail loudly rather than silently OR the planes."""
    csr, eng = _dist_engine(SSSP)
    payload = VertexProgram(name="payload-max", combine="max")
    with pytest.raises(NotImplementedError, match="OR-reduce-scatter"):
        eng.run_program_batch(payload, np.asarray([0, 1]))


# ---------------------------------------------------------------------------
# sparse (budgeted) pull: unit differential vs the dense scan, and the
# end-to-end driver crossover
# ---------------------------------------------------------------------------

def test_sparse_pull_matches_dense_scan_unit():
    """_propagate_pull_sparse must agree with the dense CSC scan on an
    arbitrary mid-traversal plane state (multi-word, with pad planes),
    and report the exact m_u edge total for the overflow contract."""
    from repro.core import bitmap
    from repro.core.vertex_program import (_propagate_pull_scan,
                                           _propagate_pull_sparse)

    csr = _awkward_graph(N, 512, seed=77)
    g = build_local_graph(csr, transpose_csr(csr))
    nb = 33                                 # two plane words, one partial
    nw = bitmap.num_words(nb)
    pmask = np.asarray(bitmap.plane_mask(nb))
    rng = np.random.default_rng(9)
    frontier = (rng.integers(0, 1 << 32, (g.n_pad, nw), dtype=np.uint32)
                & pmask)
    seen = (frontier
            | (rng.integers(0, 1 << 32, (g.n_pad, nw), dtype=np.uint32)
               & pmask))
    frontier[g.n:] = 0                      # pad vertices carry no state
    seen[g.n:] = pmask                      # pad vertices: all planes seen

    dense_new = np.asarray(_propagate_pull_scan(g, frontier)[0]) & ~seen
    # exact unseen-edge total: sum of in-degrees over any-plane-unseen
    in_deg = np.diff(np.asarray(g.in_indptr))[: g.n_pad]
    un_any = ((~seen & pmask) != 0).any(axis=1)
    m_u = int(in_deg[un_any].sum())

    new, seen2, total, _ = _propagate_pull_sparse(
        g, frontier, seen, nb, max(1 << (m_u - 1).bit_length(), 64))
    assert int(total) == m_u
    np.testing.assert_array_equal(np.asarray(new), dense_new)
    np.testing.assert_array_equal(np.asarray(seen2), seen | dense_new)

    # truncated budget: total still reports m_u so the driver retries
    if m_u > 4:
        _, _, short, _ = _propagate_pull_sparse(g, frontier, seen, nb,
                                                m_u // 2)
        assert int(short) == m_u


@pytest.mark.parametrize("batch", [1, 33])
def test_sparse_pull_runner_matches_dense_runner(batch):
    """End-to-end: a sparse_pull=True runner must produce identical
    levels to the dense runner and the per-root oracle, with the sparse
    path actually taken on tail pull levels (spied via _pull_budget) and
    the one-fetch-per-level transfer invariant intact."""
    from repro.core.scheduler import SchedulerConfig

    # big enough that the crossover rule (pb * 8 <= E) can fire
    src = np.random.default_rng(4).integers(0, 4096, 40000)
    dst = np.random.default_rng(5).integers(0, 4096, 40000)
    csr = csr_from_edges(src, dst, 4096)
    g = build_local_graph(csr, transpose_csr(csr))
    roots = np.random.default_rng(6).choice(4096, batch, replace=False)

    dense = MultiSourceBFSRunner(g, sched=SchedulerConfig(policy="pull"))
    sparse = MultiSourceBFSRunner(g, sched=SchedulerConfig(policy="pull"),
                                  sparse_pull=True)
    budgets = []
    orig = sparse._pull_budget

    def spy(m_u):
        pb = orig(m_u)
        budgets.append(pb)
        return pb

    sparse._pull_budget = spy
    want = dense.run(roots).levels
    res = sparse.run(roots)
    np.testing.assert_array_equal(res.levels, want)
    np.testing.assert_array_equal(
        np.asarray(res.levels[0], np.int64)[: 4096],
        bfs_oracle(csr, int(roots[0])))
    assert any(pb > 0 for pb in budgets)    # sparse path actually ran
    assert any(pb == 0 for pb in budgets)   # full-stream levels stay dense
    assert sparse.last_stats["host_transfers"] == res.iterations + 2
    # device-side per-plane traversed counts agree with the host recount
    from repro.core import count_traversed_edges
    deg = np.diff(csr.indptr)
    assert sum(sparse.last_stats["traversed_per_plane"]) == \
        count_traversed_edges(deg, res.levels)
