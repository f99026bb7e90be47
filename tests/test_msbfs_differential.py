"""Differential MS-BFS sweep: every engine path against every other.

Three independent implementations answer the same batch of queries —
``MultiSourceBFSRunner`` (hybrid gather pipeline, with and without the
Pallas P3 kernel), ``msbfs_reference`` (dense jit loop), and the
pure-python per-root ``bfs_oracle`` — and must agree bit-for-bit at batch
sizes that exercise partial plane words (1, 5, 31, 33, 48) on random
graphs that include isolated vertices and self-loops.

Also: oracle tests for ``DistributedBFS.run_batch`` under forced
push-only / pull-only scheduling (the hybrid path was the only one
exercised before), batches wider than one plane word, and the
``bfs_batch`` root-validation contract (ValueError out of range,
duplicates allowed).
"""
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import (BFSRunner, MultiSourceBFSRunner, SchedulerConfig,
                        bfs_oracle, build_local_graph, msbfs_reference,
                        partition_graph)
from repro.core.bfs_distributed import DistConfig, DistributedBFS
from repro.graph import csr_from_edges, transpose_csr

N = 128


def _awkward_graph(n: int, m: int, seed: int):
    """Random digraph with guaranteed isolated vertices and self-loops.

    Edges are confined to the first 3n/4 vertices (the last quarter is
    fully isolated: no in- or out-edges), and every 16th active vertex
    gets a self-loop.
    """
    rng = np.random.default_rng(seed)
    hi = (3 * n) // 4
    src = rng.integers(0, hi, m)
    dst = rng.integers(0, hi, m)
    loops = np.arange(0, hi, 16)
    src = np.concatenate([src, loops])
    dst = np.concatenate([dst, loops])
    csr = csr_from_edges(src, dst, n)
    assert (np.diff(csr.indptr)[hi:] == 0).all()      # isolates exist
    return csr, build_local_graph(csr, transpose_csr(csr))


def _roots(n: int, batch: int, seed: int) -> np.ndarray:
    """Batch of roots that always includes an isolated vertex and a
    self-loop vertex when it has room for them."""
    rng = np.random.default_rng(seed)
    roots = rng.choice(n, batch, replace=False)
    if batch >= 2:
        roots[0] = n - 1        # isolated (edges confined to [0, 3n/4))
        roots[1] = 16           # self-loop vertex
    return roots.astype(np.int32)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp-p3", "pallas-p3"])
@pytest.mark.parametrize("batch", [1, 5, 31, 33, 48])
def test_runner_vs_reference_vs_oracle(batch, use_pallas):
    csr, g = _awkward_graph(N, 512, seed=100 + batch)
    roots = _roots(N, batch, seed=batch)
    res = MultiSourceBFSRunner(g, use_pallas=use_pallas).run(roots)
    ref = np.asarray(msbfs_reference(g, roots))
    np.testing.assert_array_equal(res.levels, ref)
    for i, r in enumerate(roots):
        np.testing.assert_array_equal(res.levels[i].astype(np.int64),
                                      bfs_oracle(csr, int(r)))
    assert res.batch == batch and res.levels.shape == (batch, N)


# ---------------------------------------------------------------------------
# packed-word pipeline vs the legacy bool-plane path (tentpole differential):
# the fused propagate (Pallas kernel AND the _scatter_or_rows/segment-scan
# jnp fallbacks) must agree bit-for-bit with the bool-plane implementation
# in BOTH directions, at batch sizes that exercise partial and multiple
# plane words, on graphs with isolates and self-loops.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp-propagate", "pallas-propagate"])
@pytest.mark.parametrize("policy", ["push", "pull", "beamer"])
@pytest.mark.parametrize("batch", [1, 32, 48])
def test_packed_vs_boolplane(batch, policy, use_pallas):
    csr, g = _awkward_graph(N, 512, seed=200 + batch)
    roots = _roots(N, batch, seed=7 * batch + 1)
    sched = SchedulerConfig(policy=policy)
    packed = MultiSourceBFSRunner(g, sched,
                                  use_pallas=use_pallas).run(roots)
    boolp = MultiSourceBFSRunner(g, sched, packed=False).run(roots)
    np.testing.assert_array_equal(packed.levels, boolp.levels)
    for i, r in enumerate(roots):
        np.testing.assert_array_equal(packed.levels[i].astype(np.int64),
                                      bfs_oracle(csr, int(r)))
    assert packed.iterations == boolp.iterations
    if policy == "push":
        assert packed.pull_iters == 0 and packed.push_iters > 0
    if policy == "pull":
        assert packed.push_iters == 0 and packed.pull_iters > 0


def test_one_host_transfer_per_level():
    """Acceptance: the packed driver performs exactly ONE blocking
    device->host transfer per level — the fused int32[7] stats vector —
    plus one for the initial frontier stats and one final level readback
    (counted by the runner's ``_fetch`` wrapper)."""
    csr, g = _awkward_graph(N, 512, seed=9)
    roots = _roots(N, 32, seed=3)
    res = MultiSourceBFSRunner(g).run(roots)
    assert res.iterations > 1
    assert res.host_transfers == res.iterations + 2
    # the legacy bool-plane driver pays several blocking syncs per level
    legacy = MultiSourceBFSRunner(g, packed=False).run(roots)
    assert legacy.host_transfers >= 5 * legacy.iterations
    # single-source driver has the same one-sync structure
    r1 = BFSRunner(g).run(16)
    assert r1.host_transfers == r1.iterations + 2


def test_isolated_root_reaches_only_itself():
    csr, g = _awkward_graph(N, 512, seed=0)
    res = MultiSourceBFSRunner(g).run(np.asarray([N - 1], np.int32))
    assert res.levels[0][N - 1] == 0
    assert (res.levels[0] >= (1 << 30)).sum() == N - 1


def test_self_loop_does_not_change_levels():
    # same random edges, with and without an added self-loop at the root
    rng = np.random.default_rng(5)
    src, dst = rng.integers(0, 96, 400), rng.integers(0, 96, 400)
    csr_a = csr_from_edges(src, dst, N)
    csr_b = csr_from_edges(np.append(src, 7), np.append(dst, 7), N)
    roots = np.asarray([7, 20], np.int32)
    res_a = MultiSourceBFSRunner(
        build_local_graph(csr_a, transpose_csr(csr_a))).run(roots)
    res_b = MultiSourceBFSRunner(
        build_local_graph(csr_b, transpose_csr(csr_b))).run(roots)
    np.testing.assert_array_equal(res_a.levels, res_b.levels)


# ---------------------------------------------------------------------------
# distributed run_batch: forced directions + multi-word batches
# ---------------------------------------------------------------------------

def _dist_engine(policy: str = "beamer", shards: int = 4, seed: int = 3):
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, 64, 256), rng.integers(0, 64, 256)
    csr = csr_from_edges(src, dst, 64)
    pg = partition_graph(csr, transpose_csr(csr), shards)
    mesh = make_mesh((1,), ("data",))
    cfg = DistConfig(scheduler=SchedulerConfig(policy=policy))
    return csr, DistributedBFS(pg, mesh, cfg=cfg)


@pytest.mark.parametrize("policy", ["push", "pull"])
def test_distributed_run_batch_forced_direction(policy):
    """Push-only and pull-only batched steps must match the oracle on
    their own (the hybrid path can mask a broken direction)."""
    csr, eng = _dist_engine(policy)
    roots = np.asarray([0, 2, 5, 31, 63])
    levels = eng.run_batch(roots)
    for i, r in enumerate(roots):
        np.testing.assert_array_equal(levels[i], bfs_oracle(csr, int(r)))
    key = "pull_iters" if policy == "pull" else "push_iters"
    other = "push_iters" if policy == "pull" else "pull_iters"
    assert eng.last_stats[key] > 0 and eng.last_stats[other] == 0


def test_distributed_run_batch_wider_than_one_plane_word():
    """40 concurrent sources = 2 packed uint32 words per vertex."""
    csr, eng = _dist_engine("beamer")
    roots = np.random.default_rng(11).choice(64, 40, replace=False)
    levels = eng.run_batch(roots)
    assert levels.shape == (40, 64)
    for i, r in enumerate(roots):
        np.testing.assert_array_equal(levels[i], bfs_oracle(csr, int(r)))


# ---------------------------------------------------------------------------
# bfs_batch root-validation contract
# ---------------------------------------------------------------------------

def test_bfs_batch_rejects_out_of_range_roots():
    from repro.launch.serve import bfs_batch, build_bfs_engine
    engine, deg = build_bfs_engine("tiny-16-4", distributed=False)
    for bad in ([-1], [16], [3, -2, 5], [1 << 40]):
        with pytest.raises(ValueError, match="out of range"):
            bfs_batch(np.asarray(bad), engine=engine, out_deg=deg)
    with pytest.raises(ValueError):
        bfs_batch(np.asarray([], np.int64), engine=engine, out_deg=deg)


def test_bfs_batch_allows_duplicate_roots():
    from repro.launch.serve import bfs_batch, build_bfs_engine
    engine, deg = build_bfs_engine("tiny-16-4", distributed=False)
    out = bfs_batch(np.asarray([3, 3, 9]), engine=engine, out_deg=deg)
    assert out["batch"] == 3
    np.testing.assert_array_equal(out["levels"][0], out["levels"][1])


def test_engine_run_validates_directly():
    csr, g = _awkward_graph(N, 256, seed=1)
    with pytest.raises(ValueError):
        MultiSourceBFSRunner(g).run(np.asarray([0, N], np.int32))
    # a >= 2**31 root must error, not wrap through the int32 cast
    with pytest.raises(ValueError):
        MultiSourceBFSRunner(g).run(np.asarray([2 ** 32 + 5], np.int64))
    # float roots must error, not truncate
    with pytest.raises(ValueError, match="integers"):
        MultiSourceBFSRunner(g).run(np.asarray([5.7]))
    csr2, eng = _dist_engine()
    with pytest.raises(ValueError):
        eng.run_batch(np.asarray([-3]))
    with pytest.raises(ValueError):
        eng.run_batch(np.asarray([[1, 2]]))
