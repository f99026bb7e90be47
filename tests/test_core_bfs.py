"""System-behaviour tests: Algorithm 2 BFS vs the Algorithm 1 oracle."""
import numpy as np
import pytest
from repro.testing import given, settings, strategies as st

from repro.core import (BFSRunner, SchedulerConfig, bfs_oracle,
                        bfs_reference, build_local_graph, partition_graph)
from repro.core import bitmap
from repro.graph import csr_from_edges, get_dataset, rmat_edges, symmetrize_edges
from repro.graph.csr import transpose_csr

import jax.numpy as jnp


@pytest.fixture(scope="module")
def tiny():
    return get_dataset("tiny-16-4")


@pytest.fixture(scope="module")
def small():
    return get_dataset("small-12-8")


def test_reference_matches_oracle(tiny):
    g = build_local_graph(tiny.csr, tiny.csc)
    for root in [0, 3, 7, 15]:
        got = np.asarray(bfs_reference(g, root)).astype(np.int64)
        np.testing.assert_array_equal(got, bfs_oracle(tiny.csr, root))


@pytest.mark.parametrize("policy", ["push", "pull", "beamer", "paper"])
def test_runner_all_policies(small, policy):
    g = build_local_graph(small.csr, small.csc)
    orc = bfs_oracle(small.csr, 5)
    r = BFSRunner(g, SchedulerConfig(policy=policy)).run(5)
    np.testing.assert_array_equal(r.level.astype(np.int64), orc)


def test_hybrid_inspects_fewer_edges_than_pure_modes(small):
    """Paper Fig. 8: hybrid < push < pull in memory work on scale-free graphs."""
    g = build_local_graph(small.csr, small.csc)
    res = {p: BFSRunner(g, SchedulerConfig(policy=p)).run(2)
           for p in ("push", "pull", "beamer")}
    assert res["beamer"].edges_inspected <= res["push"].edges_inspected
    assert res["beamer"].edges_inspected <= res["pull"].edges_inspected


def test_directed_graph(tiny):
    src, dst = rmat_edges(6, 4, seed=9)
    csr = csr_from_edges(src, dst, 64)
    csc = transpose_csr(csr)
    g = build_local_graph(csr, csc)
    r = BFSRunner(g).run(1)
    np.testing.assert_array_equal(r.level.astype(np.int64), bfs_oracle(csr, 1))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10**6), st.integers(2, 5), st.booleans())
def test_bfs_property_random_graphs(seed, ef, undirected):
    """Property: Algorithm-2 levels == oracle levels on random RMATs."""
    src, dst = rmat_edges(7, ef, seed=seed)
    if undirected:
        src, dst = symmetrize_edges(src, dst)
    csr = csr_from_edges(src, dst, 128)
    csc = transpose_csr(csr)
    g = build_local_graph(csr, csc)
    root = seed % 128
    r = BFSRunner(g).run(root)
    np.testing.assert_array_equal(r.level.astype(np.int64),
                                  bfs_oracle(csr, root))


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 2**20), min_size=1, max_size=64),
       st.integers(1, 2**20))
def test_bitmap_roundtrip_property(indices, nbits):
    nbits = max(nbits, max(indices) + 1)
    w = bitmap.from_indices_dense(jnp.asarray(np.array(indices)), nbits)
    mask = np.asarray(bitmap.unpack(w, nbits))
    want = np.zeros(bitmap.num_words(nbits) * 32, bool)[:nbits]
    want[np.asarray(indices)] = True
    np.testing.assert_array_equal(mask, want)
    assert int(bitmap.popcount(w)) == int(want.sum())
    got = np.asarray(bitmap.test_bits(w, jnp.asarray(np.array(indices))))
    assert got.all()


def test_bitmap_pack_unpack_inverse():
    rng = np.random.default_rng(0)
    mask = jnp.asarray(rng.random(4096) < 0.3)
    np.testing.assert_array_equal(
        np.asarray(bitmap.unpack(bitmap.pack(mask), 4096)), np.asarray(mask))


def test_partition_preserves_edges(small):
    pg = partition_graph(small.csr, small.csc, 4)
    assert pg.num_edges == small.csr.num_edges
    # every reindexed neighbor maps back to a valid original vertex
    from repro.core.partition import unreindex
    ids = pg.out_indices[pg.out_indices >= 0]
    orig = unreindex(ids.astype(np.int64), pg.num_shards, pg.verts_per_shard)
    assert (orig < small.csr.num_vertices).all()


def test_levels_are_valid_bfs_levels(small):
    """Property: level(child) <= level(parent)+1 along every edge, and every
    reached vertex (level>0) has a parent at level-1."""
    g = build_local_graph(small.csr, small.csc)
    r = BFSRunner(g).run(0)
    lev = r.level.astype(np.int64)
    csr = small.csr
    INF = 2 ** 30
    for v in range(csr.num_vertices):
        if lev[v] >= INF:
            continue
        for u in csr.neighbors(v):
            assert lev[u] <= lev[v] + 1
    csc = small.csc
    for v in range(csr.num_vertices):
        if 0 < lev[v] < INF:
            parents = csc.neighbors(v)
            assert (lev[parents] == lev[v] - 1).any()


def _bfs_loop(csr, root):
    """Per-vertex queue BFS (Algorithm 1), the loop form ``bfs_oracle``
    replaced: kept here as the oracle's own reference."""
    from collections import deque
    inf = int(np.iinfo(np.int32).max)
    level = np.full(csr.num_vertices, inf, dtype=np.int64)
    level[root] = 0
    q = deque([root])
    while q:
        v = q.popleft()
        for u in csr.neighbors(v):
            if level[u] == inf:
                level[u] = level[v] + 1
                q.append(int(u))
    return level


@pytest.mark.parametrize("name", ["tiny-16-4", "small-12-8",
                                  "directed-rmat10-4"])
def test_vectorized_oracle_matches_loop(name):
    """The level-synchronous numpy oracle equals the queue BFS exactly,
    unreached vertices included, on undirected and directed graphs."""
    if name.startswith("directed"):
        src, dst = rmat_edges(10, 4, seed=3)
        csr = csr_from_edges(src, dst, 1 << 10)
    else:
        csr = get_dataset(name).csr
    deg = np.diff(csr.indptr)
    roots = [0, int(np.argmax(deg)), int(np.flatnonzero(deg == 0)[0])
             if (deg == 0).any() else 1]
    for root in roots:
        want = _bfs_loop(csr, root)
        got = bfs_oracle(csr, root)
        unreached = want == np.iinfo(np.int32).max
        np.testing.assert_array_equal(got[~unreached], want[~unreached])
        assert (got[unreached] == int(np.asarray(2 ** 30))).all()
        assert got.dtype == np.int64
