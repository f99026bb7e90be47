"""Tiled-vs-whole-VMEM differential suite for the fused P2->P3 propagate.

The row-tiled kernel (``msbfs_propagate_planes_tiled`` + the edge
bucketing in ``kernels.ops``) must be bit-exact against BOTH the
whole-VMEM kernel and the pure-jnp oracle on every case the tiling could
plausibly break: targets straddling tile boundaries, hub vertices whose
edges span / concentrate on tiles, batch widths around the word boundary
(B = 1 / 32 / 48), both combine ops, and the engine/distributed layers
that select it.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.compat import make_mesh
from repro.core import bfs_oracle, partition_graph
from repro.core.bfs_distributed import DistConfig, DistributedBFS
from repro.core.bfs_local import build_local_graph
from repro.core.scheduler import SchedulerConfig
from repro.core.vertex_program import (MultiSourceBFSRunner, SSSPRunner,
                                       msbfs_reference)
from repro.graph import csr_from_edges, transpose_csr, uniform_edges
from repro.kernels import ops, ref

TILE = 16          # forced tile size for the differential cases
BLOCK = 32         # forced edge-chunk size (small => many chunks per tile)


def _planes(n, nw, seed):
    rng = np.random.default_rng(seed)
    frontier = rng.integers(0, 2**32, (n, nw), dtype=np.uint32)
    seen = rng.integers(0, 2**32, (n, nw), dtype=np.uint32)
    return frontier, seen


def _assert_tiled_matches(frontier, seen, src, tgt, valid, op="or",
                          tile_rows=TILE, block_edges=BLOCK):
    """Tiled == whole-VMEM == jnp oracle, bit for bit (new/seen/count)."""
    n = frontier.shape[0]
    args = (jnp.asarray(frontier), jnp.asarray(seen), jnp.asarray(src),
            jnp.asarray(tgt), jnp.asarray(valid))
    got_t = ops.msbfs_propagate(*args, block_edges=block_edges,
                                interpret=True, op=op, tile_rows=tile_rows)
    got_w = ops.msbfs_propagate(*args, block_edges=block_edges,
                                interpret=True, op=op, tile_rows=0)
    ok = (valid & (src >= 0) & (src < n) & (tgt >= 0) & (tgt < n))
    msg = np.where(ok[:, None], frontier[np.clip(src, 0, n - 1)], 0)
    want = ref.msbfs_propagate_msgs_ref(
        jnp.asarray(seen), jnp.asarray(msg), jnp.asarray(tgt),
        jnp.asarray(ok), op=op)
    for g, w, o, name in zip(got_t, got_w, want, ("new", "seen", "cnt")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"tiled vs whole: {name}")
        np.testing.assert_array_equal(
            np.asarray(g).reshape(-1), np.asarray(o).reshape(-1),
            err_msg=f"tiled vs oracle: {name}")


@pytest.mark.parametrize("batch", [1, 32, 48])
@pytest.mark.parametrize("op", ["or", "max"])
def test_tiled_differential_random(batch, op):
    """Random edges at B = 1 / 32 / 48 (nw = 1, 1, 2), both combine ops,
    invalid and out-of-range slots mixed in."""
    nw = (batch + 31) // 32
    n, m = 100, 700
    frontier, seen = _planes(n, nw, seed=batch * 7 + len(op))
    rng = np.random.default_rng(batch * 13 + len(op))
    src = rng.integers(-2, n + 3, m).astype(np.int32)
    tgt = rng.integers(-2, n + 3, m).astype(np.int32)
    valid = rng.random(m) < 0.85
    _assert_tiled_matches(frontier, seen, src, tgt, valid, op=op)


def test_tiled_tile_boundary_straddling():
    """Every edge targets a row adjacent to a tile boundary: the kernel's
    global->tile-local index arithmetic is exercised at both edges of
    every tile."""
    n, nw = 8 * TILE, 2
    frontier, seen = _planes(n, nw, seed=3)
    bounds = np.arange(TILE, n, TILE, dtype=np.int32)
    tgt = np.concatenate([bounds - 1, bounds, bounds + 1,
                          np.asarray([0, n - 1], np.int32)])
    tgt = np.tile(tgt, 5)
    rng = np.random.default_rng(4)
    src = rng.integers(0, n, tgt.size).astype(np.int32)
    valid = np.ones(tgt.size, bool)
    _assert_tiled_matches(frontier, seen, src, tgt, valid)


@pytest.mark.parametrize("op", ["or", "max"])
def test_tiled_hub_source_spans_tiles(op):
    """One hub vertex's out-list spans >= 3 row tiles (its frontier word
    is gathered once per edge but scattered into many tiles)."""
    n, nw = 6 * TILE, 1
    frontier, seen = _planes(n, nw, seed=11)
    hub = 7
    tgt = np.arange(0, 5 * TILE, 1, dtype=np.int32)       # tiles 0..4
    src = np.full(tgt.size, hub, np.int32)
    valid = np.ones(tgt.size, bool)
    _assert_tiled_matches(frontier, seen, src, tgt, valid, op=op)


def test_tiled_hub_target_overflows_chunk():
    """Degree-aware budget tiling: one hub TARGET draws far more edges
    than one ``block_edges`` chunk holds, so its tile must be allocated
    multiple chunks while other tiles stay small."""
    n, nw = 5 * TILE, 1
    frontier, seen = _planes(n, nw, seed=17)
    m = 6 * BLOCK + 11                       # >6 chunks aimed at one row
    rng = np.random.default_rng(18)
    src = rng.integers(0, n, m).astype(np.int32)
    tgt = np.full(m, 2 * TILE + 3, np.int32)  # all into tile 2
    # plus a sprinkle elsewhere so other tiles are non-empty
    tgt[::13] = rng.integers(0, n, tgt[::13].size)
    valid = np.ones(m, bool)
    _assert_tiled_matches(frontier, seen, src, tgt, valid)


def test_tiled_empty_tiles_still_commit_p3():
    """Tiles receiving no edges must still run P3 (new=0 against their
    seen) — their rows must come back exact, not stale."""
    n, nw = 7 * TILE, 1
    frontier, seen = _planes(n, nw, seed=23)
    tgt = np.full(40, 3, np.int32)           # all edges into tile 0
    src = np.arange(40, dtype=np.int32)
    valid = np.ones(40, bool)
    _assert_tiled_matches(frontier, seen, src, tgt, valid)


def test_tiled_all_edges_invalid():
    n, nw = 3 * TILE, 1
    frontier, seen = _planes(n, nw, seed=29)
    m = 50
    src = np.arange(m, dtype=np.int32)
    tgt = np.arange(m, dtype=np.int32) % n
    valid = np.zeros(m, bool)
    _assert_tiled_matches(frontier, seen, src, tgt, valid)


def test_tiled_rows_not_tile_multiple():
    """n not divisible by tile_rows: the pad rows (seen = all-ones) must
    never surface as discoveries or counts."""
    for n in (TILE + 1, 3 * TILE - 1, 37):
        frontier, seen = _planes(n, 1, seed=n)
        rng = np.random.default_rng(n + 1)
        m = 200
        src = rng.integers(0, n, m).astype(np.int32)
        tgt = rng.integers(0, n, m).astype(np.int32)
        _assert_tiled_matches(frontier, seen, src, tgt, np.ones(m, bool))


@pytest.mark.parametrize("op", ["or", "max"])
def test_sequential_loop_body_matches_vectorized(op):
    """The compiled-TPU per-edge RMW loop and the interpret-mode
    vectorized chunk scatter are the same function: force each body of
    both kernels under the interpreter and compare bit for bit."""
    from repro.kernels.msbfs_propagate import (msbfs_propagate_planes,
                                               msbfs_propagate_planes_tiled)
    n, nw, m = 4 * TILE, 2, 8 * BLOCK
    frontier, seen = _planes(n + 1, nw, seed=5)
    frontier[n] = 0
    seen[n] = np.uint32(0xFFFFFFFF)       # trash-row form of the whole kernel
    rng = np.random.default_rng(11)
    src = jnp.asarray(rng.integers(0, n + 1, m).astype(np.int32))
    tgt = jnp.asarray(rng.integers(0, n + 1, m).astype(np.int32))
    loop, vec = (msbfs_propagate_planes(
        jnp.asarray(frontier), jnp.asarray(seen), src, tgt,
        block_edges=BLOCK, interpret=True, op=op, vector_scatter=v)
        for v in (False, True))
    for a, b, name in zip(loop, vec, ("new", "seen", "cnt")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"whole kernel: {name}")
    fr, sn = _planes(n, nw, seed=6)
    half = m // 2
    msg = jnp.asarray(fr)[src[:half] % n]
    tg = tgt[:half] % n
    sm, st, ct = ops._bucket_edges_by_tile(
        msg, tg, jnp.ones(half, bool), n // TILE, TILE, BLOCK)
    loop, vec = (msbfs_propagate_planes_tiled(
        jnp.asarray(sn), sm.reshape(-1), st, ct, tile_rows=TILE,
        block_edges=BLOCK,
        interpret=True, op=op, vector_scatter=v)
        for v in (False, True))
    for a, b, name in zip(loop, vec, ("new", "seen", "cnt")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"tiled kernel: {name}")


# ---------------------------------------------------------------------------
# bucketing invariants (the host/jnp side of the tiled contract)
# ---------------------------------------------------------------------------

def test_bucket_edges_by_tile_invariants():
    n, nw, m, tr, c = 100, 2, 333, 16, 32
    t = -(-n // tr)
    rng = np.random.default_rng(5)
    msg = rng.integers(0, 2**32, (m, nw), dtype=np.uint32)
    tgt = rng.integers(0, n, m).astype(np.int32)
    ok = rng.random(m) < 0.8
    msg[~ok] = 0
    sm, st, ct = (np.asarray(x) for x in ops._bucket_edges_by_tile(
        jnp.asarray(msg), jnp.asarray(tgt), jnp.asarray(ok), t, tr, c))
    nc = -(-m // c) + t
    assert ct.shape == (nc,) and sm.shape == (nc * c, nw)
    # nondecreasing chunk->tile map covering every tile (the kernel's
    # accumulator-persistence + P3-once-per-tile invariant)
    assert (np.diff(ct) >= 0).all()
    np.testing.assert_array_equal(np.unique(ct), np.arange(t))
    # every streamed slot's target lies inside its chunk's tile
    slot_tile = np.repeat(ct, c)
    assert ((st >= slot_tile * tr) & (st < (slot_tile + 1) * tr)).all()
    # the multiset of valid (tgt, msg) pairs survives exactly; pad slots
    # carry msg = 0 (the combine identity)
    want = sorted((int(tgt[e]), msg[e].tobytes()) for e in range(m) if ok[e])
    got = sorted((int(st[i]), sm[i].tobytes()) for i in range(nc * c)
                 if sm[i].any())
    assert got == want


def test_propagate_plan_selection():
    # plane rows pad to 128 lanes in VMEM, so under the default 16 MiB
    # budget only graphs of a few thousand vertices stay whole-VMEM, at
    # any batch up to 128 planes words; rmat16 and larger tile
    assert not ops.propagate_plan(4000, 1)["tiled"]
    assert not ops.propagate_plan(4000, 4)["tiled"]
    assert ops.propagate_plan(1 << 16, 1)["tiled"]
    assert ops.propagate_plan(1 << 20, 1)["tiled"]
    assert ops.propagate_plan(1 << 16, 4)["tiled"]
    # the tile rule counts the padded row: 16 MiB / (8 x 512 B) rows
    assert ops.propagate_plan(1 << 20, 1)["tile_rows"] == 4096
    assert ops._auto_block_edges(1 << 30, 1) == ops.MAX_BLOCK_EDGES
    # explicit budget override + forced modes
    p = ops.propagate_plan(1000, 1, vmem_bytes=1024)
    assert p["tiled"] and p["tile_rows"] >= 8
    assert p["num_tiles"] == -(-1000 // p["tile_rows"])
    assert not ops.propagate_plan(1 << 20, 1, tile_rows=0)["tiled"]
    assert ops.propagate_plan(100, 1, tile_rows=16)["num_tiles"] == 7
    with pytest.raises(ValueError):
        ops.propagate_plan(100, 1, tile_rows=-3)


# ---------------------------------------------------------------------------
# msgs-form entry (the distributed pull's contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["or", "max"])
def test_msbfs_propagate_msgs_vs_ref(op):
    n, nw, m = 90, 2, 400
    rng = np.random.default_rng(41)
    seen = rng.integers(0, 2**32, (n, nw), dtype=np.uint32)
    msg = rng.integers(0, 2**32, (m, nw), dtype=np.uint32)
    tgt = rng.integers(-3, n + 3, m).astype(np.int32)
    valid = rng.random(m) < 0.8
    got = ops.msbfs_propagate_msgs(
        jnp.asarray(seen), jnp.asarray(msg), jnp.asarray(tgt),
        jnp.asarray(valid), tile_rows=TILE, block_edges=BLOCK,
        interpret=True, op=op)
    want = ref.msbfs_propagate_msgs_ref(
        jnp.asarray(seen), jnp.asarray(msg), jnp.asarray(tgt),
        jnp.asarray(valid), op=op)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g).reshape(-1),
                                      np.asarray(w).reshape(-1))


# ---------------------------------------------------------------------------
# engine + distributed layers select / survive the tiled kernel
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def graph():
    src, dst = uniform_edges(300, 1500, seed=9)
    csr = csr_from_edges(src, dst, 300)
    return csr, build_local_graph(csr, transpose_csr(csr))


@pytest.mark.parametrize("batch", [1, 32, 48])
def test_engine_tiled_matches_reference(graph, batch):
    _, g = graph
    roots = np.random.default_rng(batch).choice(300, batch,
                                                replace=False).astype(np.int32)
    want = np.asarray(msbfs_reference(g, roots))
    got = MultiSourceBFSRunner(g, use_pallas=True,
                               tile_rows=64).run(roots).levels
    np.testing.assert_array_equal(got, want)
    # whole-VMEM arm of the same differential
    got_w = MultiSourceBFSRunner(g, use_pallas=True,
                                 tile_rows=0).run(roots).levels
    np.testing.assert_array_equal(got_w, want)


def test_engine_tiled_pull_only(graph):
    """Force the budgeted Pallas pull so the tiled kernel runs in the
    pull direction too (child/parent swapped relative to push)."""
    _, g = graph
    roots = np.arange(8, dtype=np.int32)
    want = np.asarray(msbfs_reference(g, roots))
    r = MultiSourceBFSRunner(g, SchedulerConfig(policy="pull"),
                             use_pallas=True, tile_rows=32)
    np.testing.assert_array_equal(r.run(roots).levels, want)


def test_sssp_rides_tiled_propagate(graph):
    _, g = graph
    roots = np.arange(5, dtype=np.int32)
    want = SSSPRunner(g).run(roots).levels
    got = SSSPRunner(g, use_pallas=True, tile_rows=32).run(roots).levels
    np.testing.assert_array_equal(got, want)


def test_distributed_pull_uses_tiled_kernel(graph):
    """DistConfig(use_pallas=True): the batched pull runs the msgs-form
    tiled kernel with tile_rows = verts_per_shard (one tile per PE) and
    must match the per-root oracle exactly."""
    csr, _ = graph
    pg = partition_graph(csr, transpose_csr(csr), 4)
    mesh = make_mesh((1,), ("data",))
    roots = np.asarray([0, 3, 11, 200], np.int64)
    cfg = DistConfig(use_pallas=True,
                     scheduler=SchedulerConfig(policy="pull"))
    got = DistributedBFS(pg, mesh, cfg=cfg).run_batch(roots)
    jnp_cfg = DistConfig(scheduler=SchedulerConfig(policy="pull"))
    want = DistributedBFS(pg, mesh, cfg=jnp_cfg).run_batch(roots)
    np.testing.assert_array_equal(got, want)
    for i, r in enumerate(roots):
        np.testing.assert_array_equal(got[i], bfs_oracle(csr, int(r)))


@pytest.mark.slow
def test_tiled_auto_selection_medium_graph():
    """End-to-end auto-select on a graph big enough that the default plan
    tiles (via a squeezed VMEM budget env knob is NOT used — instead the
    tile_rows=None auto rule is exercised directly through plan + a
    forced-tile engine run on a mid-size rmat graph)."""
    from repro.graph.generators import rmat_edges
    from repro.graph.csr import csr_from_edges as _cfe
    n = 1 << 13
    src, dst = rmat_edges(13, 8, seed=1)
    csr = _cfe(src, dst, n)
    g = build_local_graph(csr, transpose_csr(csr))
    roots = np.random.default_rng(0).choice(
        np.flatnonzero(np.diff(csr.indptr) > 0), 32,
        replace=False).astype(np.int32)
    want = np.asarray(msbfs_reference(g, roots))
    got = MultiSourceBFSRunner(g, use_pallas=True,
                               tile_rows=1024).run(roots).levels
    np.testing.assert_array_equal(got, want)
