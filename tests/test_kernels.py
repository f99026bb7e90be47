"""Per-kernel allclose tests vs the pure-jnp oracles (interpret mode).

Sweeps shapes/dtypes per the assignment; property-based bit-level checks via
hypothesis.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from repro.testing import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.bitmap_update import bitmap_update
from repro.kernels.csr_gather import gather_pages
from repro.kernels.pull_spmv import pull_spmv_blocks


# ---------------------------------------------------------------------------
# bitmap_update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [8, 16, 64, 256])
@pytest.mark.parametrize("block_rows", [8, 16])
def test_bitmap_update_shapes(rows, block_rows):
    if rows % block_rows:
        pytest.skip("block must divide rows")
    rng = np.random.default_rng(rows * 31 + block_rows)
    cand = jnp.asarray(rng.integers(0, 2**32, (rows, 128), dtype=np.uint32))
    vis = jnp.asarray(rng.integers(0, 2**32, (rows, 128), dtype=np.uint32))
    nf, vo, cnt = bitmap_update(cand, vis, block_rows=block_rows)
    nf_r, vo_r, cnt_r = ref.bitmap_update_ref(cand, vis)
    np.testing.assert_array_equal(np.asarray(nf), np.asarray(nf_r))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(vo_r))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_r))


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(0, 2**31 - 1))
def test_bitmap_update_property(seed_a, seed_b):
    rng = np.random.default_rng([seed_a, seed_b])
    cand = jnp.asarray(rng.integers(0, 2**32, (8, 128), dtype=np.uint32))
    vis = jnp.asarray(rng.integers(0, 2**32, (8, 128), dtype=np.uint32))
    nf, vo, cnt = bitmap_update(cand, vis, block_rows=8)
    # invariants: new ∩ visited_in = ∅; visited_out = visited_in ∪ new;
    # count == popcount(new); idempotence on re-application.
    assert int(jnp.sum(jax.lax.population_count(nf & vis))) == 0
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(vis | nf))
    assert int(cnt[0, 0]) == int(
        jnp.sum(jax.lax.population_count(nf).astype(jnp.int32)))
    nf2, vo2, cnt2 = bitmap_update(cand, vo, block_rows=8)
    assert int(cnt2[0, 0]) == 0 and bool((vo2 == vo).all())


def test_fused_frontier_update_flat_odd_sizes():
    for w in [1, 31, 128, 1000, 4096, 5000]:
        rng = np.random.default_rng(w)
        c = jnp.asarray(rng.integers(0, 2**32, (w,), dtype=np.uint32))
        v = jnp.asarray(rng.integers(0, 2**32, (w,), dtype=np.uint32))
        nf, vo, cnt = ops.fused_frontier_update(c, v)
        np.testing.assert_array_equal(np.asarray(nf), np.asarray(c & ~v))
        np.testing.assert_array_equal(np.asarray(vo), np.asarray(v | (c & ~v)))


def test_pad_rows_to_block_never_degrades_to_one_row():
    """Regression: the old divisor hunt returned block_rows=1 for prime
    row counts (a rows-step grid of 1-row blocks); the pad plan must keep
    full-size blocks and only pad the row count up."""
    assert ops._pad_rows_to_block(17) == (32, 16)       # prime
    assert ops._pad_rows_to_block(16) == (16, 16)       # exact
    assert ops._pad_rows_to_block(5) == (5, 5)          # under the cap
    assert ops._pad_rows_to_block(1) == (1, 1)
    assert ops._pad_rows_to_block(30) == (32, 16)
    for rows in range(1, 200):
        rows_pad, block = ops._pad_rows_to_block(rows)
        assert rows_pad % block == 0 and rows_pad >= rows
        assert block == min(rows, 16)                   # never 1-row-deep


def test_fused_frontier_update_prime_rows_unchanged():
    """Prime row count (w = 17 * 128 -> 17 rows) through both P3 wrappers:
    2-step grid of 16-row blocks, results identical to the jnp oracle."""
    w = 17 * 128
    rng = np.random.default_rng(17)
    c = rng.integers(0, 2**32, (w,), dtype=np.uint32)
    v = rng.integers(0, 2**32, (w,), dtype=np.uint32)
    nf, vo, cnt = ops.fused_frontier_update(jnp.asarray(c), jnp.asarray(v))
    want_new = c & ~v
    np.testing.assert_array_equal(np.asarray(nf), want_new)
    np.testing.assert_array_equal(np.asarray(vo), v | want_new)
    assert int(cnt) == int(np.unpackbits(want_new.view(np.uint8)).sum())
    cb = np.stack([c, rng.integers(0, 2**32, w, dtype=np.uint32)])
    vb = np.stack([v, rng.integers(0, 2**32, w, dtype=np.uint32)])
    nfb, vob, cnts = ops.fused_frontier_update_batch(jnp.asarray(cb),
                                                     jnp.asarray(vb))
    np.testing.assert_array_equal(np.asarray(nfb), cb & ~vb)
    np.testing.assert_array_equal(np.asarray(vob), vb | (cb & ~vb))
    for i in range(2):
        assert int(cnts[i]) == int(
            np.unpackbits((cb[i] & ~vb[i]).view(np.uint8)).sum())


# ---------------------------------------------------------------------------
# msbfs_propagate (fused P2->P3 gather/scatter-OR over packed plane words)
# ---------------------------------------------------------------------------

def _propagate_case(n_rows, nw, m, seed):
    rng = np.random.default_rng(seed)
    frontier = rng.integers(0, 2**32, (n_rows, nw), dtype=np.uint32)
    frontier[-1] = 0                       # trash-row contract
    seen = rng.integers(0, 2**32, (n_rows, nw), dtype=np.uint32)
    seen[-1] = 0xFFFFFFFF
    src = rng.integers(0, n_rows, m, dtype=np.int32)   # duplicates likely
    tgt = rng.integers(0, n_rows, m, dtype=np.int32)
    return (jnp.asarray(frontier), jnp.asarray(seen),
            jnp.asarray(src), jnp.asarray(tgt))


@pytest.mark.parametrize("n_rows,nw,m,block", [
    (33, 1, 64, 64), (65, 2, 128, 32), (129, 1, 256, 256), (17, 3, 96, 16),
])
def test_msbfs_propagate_parity(n_rows, nw, m, block):
    """Kernel vs the jnp per-bit-plane oracle (bitmap._scatter_or_rows)."""
    from repro.kernels.msbfs_propagate import msbfs_propagate_planes
    frontier, seen, src, tgt = _propagate_case(n_rows, nw, m, seed=m + nw)
    got = msbfs_propagate_planes(frontier, seen, src, tgt,
                                 block_edges=block, interpret=True)
    want = ref.msbfs_propagate_planes_ref(frontier, seen, src, tgt)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("op", ["or", "max"])
def test_msbfs_propagate_combine_op_parity(op):
    """Generalized combine: the kernel's op must match the oracle's, on a
    case where the two combines genuinely disagree (duplicate targets
    with word values whose OR is not their max)."""
    from repro.kernels.msbfs_propagate import msbfs_propagate_planes
    frontier, seen, src, tgt = _propagate_case(65, 2, 192, seed=21)
    # force colliding targets so OR-accumulation != max-selection
    tgt = tgt.at[: 64].set(tgt[0])
    got = msbfs_propagate_planes(frontier, seen, src, tgt,
                                 block_edges=32, interpret=True, op=op)
    want = ref.msbfs_propagate_planes_ref(frontier, seen, src, tgt, op=op)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    other = ref.msbfs_propagate_planes_ref(
        frontier, seen, src, tgt, op="max" if op == "or" else "or")
    assert not np.array_equal(np.asarray(want[0]), np.asarray(other[0]))


def test_msbfs_propagate_rejects_unknown_op():
    from repro.kernels.msbfs_propagate import msbfs_propagate_planes
    frontier, seen, src, tgt = _propagate_case(17, 1, 8, seed=1)
    with pytest.raises(ValueError, match="op"):
        msbfs_propagate_planes(frontier, seen, src, tgt, interpret=True,
                               op="xor")
    with pytest.raises(ValueError, match="op"):
        ref.msbfs_propagate_planes_ref(frontier, seen, src, tgt, op="xor")


def test_msbfs_propagate_wrapper_masks_and_pads():
    """ops.msbfs_propagate: invalid / OOR edges drop, count is exact, and
    the scatter-OR matches a per-edge numpy loop (independent oracle)."""
    rng = np.random.default_rng(5)
    n, nw, m = 50, 2, 777                  # m not a block multiple
    frontier = rng.integers(0, 2**32, (n, nw), dtype=np.uint32)
    seen = rng.integers(0, 2**32, (n, nw), dtype=np.uint32)
    src = rng.integers(-2, n + 3, m).astype(np.int32)
    tgt = rng.integers(-2, n + 3, m).astype(np.int32)
    valid = rng.random(m) < 0.7
    new, vout, cnt = ops.msbfs_propagate(
        jnp.asarray(frontier), jnp.asarray(seen), jnp.asarray(src),
        jnp.asarray(tgt), jnp.asarray(valid), block_edges=128)
    cand = np.zeros_like(frontier)
    for e in range(m):
        if valid[e] and 0 <= src[e] < n and 0 <= tgt[e] < n:
            cand[tgt[e]] |= frontier[src[e]]
    want_new = cand & ~seen
    np.testing.assert_array_equal(np.asarray(new), want_new)
    np.testing.assert_array_equal(np.asarray(vout), seen | want_new)
    assert int(cnt) == int(np.unpackbits(want_new.view(np.uint8)).sum())


def test_msbfs_propagate_small_budgets_single_compile():
    """Regression: tiny edge budgets (m < block_edges) used to bake the
    raw m into the static block size, compiling a fresh pallas_call per
    distinct small m.  All small budgets must now pad up to ONE fixed
    block shape — exactly one jit cache entry across differing waves."""
    from repro.kernels.msbfs_propagate import msbfs_propagate_planes
    if not (hasattr(msbfs_propagate_planes, "clear_cache")
            and hasattr(msbfs_propagate_planes, "_cache_size")):
        pytest.skip("jit cache introspection unavailable on this JAX")
    msbfs_propagate_planes.clear_cache()
    n, nw = 12, 1
    rng = np.random.default_rng(2)
    f = jnp.asarray(rng.integers(0, 2**32, (n, nw), dtype=np.uint32))
    s = jnp.zeros((n, nw), jnp.uint32)
    outs = {}
    for m in (3, 7, 13, 50, 640):
        src = jnp.arange(m, dtype=jnp.int32) % n
        tgt = (jnp.arange(m, dtype=jnp.int32) * 3) % n
        outs[m] = ops.msbfs_propagate(f, s, src, tgt,
                                      jnp.ones((m,), bool), interpret=True)
    assert msbfs_propagate_planes._cache_size() == 1
    # and the padded runs still match the per-edge oracle
    for m, (new, vout, cnt) in outs.items():
        cand = np.zeros((n, nw), np.uint32)
        for e in range(m):
            cand[(e * 3) % n] |= np.asarray(f)[e % n]
        np.testing.assert_array_equal(np.asarray(new), cand)
        assert int(cnt) == int(np.unpackbits(cand.view(np.uint8)).sum())


def test_scatter_or_rows_matches_loop():
    """bitmap._scatter_or_rows (the jnp fallback): duplicates OR together,
    OOR rows (negative or >= r) drop, existing bits survive."""
    from repro.core import bitmap
    rng = np.random.default_rng(11)
    r, nw, m = 40, 3, 500
    words = rng.integers(0, 2**32, (r, nw), dtype=np.uint32)
    idx = rng.integers(-4, r + 6, m).astype(np.int32)
    msg = rng.integers(0, 2**32, (m, nw), dtype=np.uint32)
    want = words.copy()
    for e in range(m):
        if 0 <= idx[e] < r:
            want[idx[e]] |= msg[e]
    got = bitmap._scatter_or_rows(jnp.asarray(words), jnp.asarray(idx),
                                  jnp.asarray(msg))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_segment_or_rows_matches_loop():
    """bitmap.segment_or_rows: inclusive segmented OR scan over packed
    rows (the scan-based pull propagate's reduction primitive)."""
    from repro.core import bitmap
    rng = np.random.default_rng(13)
    e_, nw = 300, 2
    msg = rng.integers(0, 2**32, (e_, nw), dtype=np.uint32)
    first = np.zeros(e_, bool)
    first[np.sort(rng.choice(e_, 25, replace=False))] = True
    first[0] = True
    seg = np.cumsum(first).astype(np.int32)       # segment id per row
    got = np.asarray(bitmap.segment_or_rows(jnp.asarray(msg),
                                            jnp.asarray(seg)))
    want = np.zeros_like(msg)
    cur = np.zeros(nw, np.uint32)
    for e in range(e_):
        cur = msg[e].copy() if first[e] else (cur | msg[e])
        want[e] = cur
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# csr_gather (HBM reader)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_pages,page,m", [
    (8, 128, 4), (32, 256, 17), (64, 512, 64), (128, 128, 1),
])
def test_gather_pages(num_pages, page, m):
    rng = np.random.default_rng(num_pages + page + m)
    edges = jnp.asarray(
        rng.integers(0, 10**6, (num_pages, page), dtype=np.int32))
    pids = jnp.asarray(rng.integers(0, num_pages, (m,), dtype=np.int32))
    out = gather_pages(edges, pids)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.gather_pages_ref(edges, pids)))


def test_page_table_covers_all_neighbor_lists():
    rng = np.random.default_rng(7)
    page = 64
    degrees = rng.integers(0, 200, 50)
    starts = np.concatenate([[0], np.cumsum(degrees)[:-1]])
    total = int(degrees.sum())
    edges = rng.integers(0, 1000, ((total + page - 1) // page) * page,
                         dtype=np.int32)
    pids, owner, offs = ops.build_page_table(starts, degrees, page, 512)
    got = np.asarray(ops.read_neighbor_pages(jnp.asarray(edges),
                                             jnp.asarray(pids), page))
    # reassemble each vertex's list from its fetched pages and compare
    for v in range(50):
        if degrees[v] == 0:
            continue
        items = [i for i in range(len(owner)) if owner[i] == v]
        parts = []
        need = degrees[v]
        for j, i in enumerate(items):
            lo = offs[i]
            take = min(need, page - lo)
            parts.append(got[i][lo: lo + take])
            need -= take
        want = edges[starts[v]: starts[v] + degrees[v]]
        np.testing.assert_array_equal(np.concatenate(parts), want)


# ---------------------------------------------------------------------------
# pull_spmv (MXU boolean SpMV)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,lanes", [(128, 1), (128, 8), (128, 128), (256, 4)])
@pytest.mark.parametrize("density", [0.01, 0.2])
def test_pull_spmv(b, lanes, density):
    rng = np.random.default_rng(b + lanes)
    nb, rb, cb = 12, 4, 4
    blocks = jnp.asarray((rng.random((nb, b, b)) < density)
                         .astype(np.float32)).astype(jnp.bfloat16)
    brow = jnp.asarray(np.sort(rng.integers(0, rb, nb)).astype(np.int32))
    bcol = jnp.asarray(rng.integers(0, cb, nb, dtype=np.int32))
    f = jnp.asarray((rng.random((cb, b, lanes)) < 0.3)
                    .astype(np.float32)).astype(jnp.bfloat16)
    got = ops.pull_spmv(blocks, brow, bcol, f, rb)
    want = ref.pull_spmv_blocks_ref(blocks, brow, bcol, None, f, rb) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pull_spmv_is_boolean_semiring():
    """OR-AND semiring result == reachability through one block step."""
    rng = np.random.default_rng(3)
    b = 128
    a_np = (rng.random((b, b)) < 0.05)
    f_np = (rng.random((b, 1)) < 0.5)
    blocks = jnp.asarray(a_np[None].astype(np.float32)).astype(jnp.bfloat16)
    f = jnp.asarray(f_np[None].astype(np.float32)).astype(jnp.bfloat16)
    got = np.asarray(ops.pull_spmv(blocks, jnp.zeros(1, jnp.int32),
                                   jnp.zeros(1, jnp.int32), f, 1))[0, :, 0]
    want = (a_np @ f_np.astype(np.int64))[:, 0] > 0
    np.testing.assert_array_equal(got, want)
