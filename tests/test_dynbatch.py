"""Dynamic-batching BFS serving driver (``repro.launch.dynbatch``).

The scheduler is driven deterministically with an injected fake clock
(no worker thread): N single-root submits inside one window must be
served by exactly ONE MS-BFS wave whose futures all match ``bfs_oracle``.
Also covers the max_batch cap, plane-slot padding, backpressure,
drain/shutdown, root validation, the threaded real-clock mode, and the
distributed engine behind the same frontend.
"""
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import (MultiSourceBFSRunner, bfs_oracle, bitmap,
                        build_local_graph, partition_graph)
from repro.core.bfs_distributed import DistConfig, DistributedBFS
from repro.graph import csr_from_edges, transpose_csr, uniform_edges
from repro.launch.dynbatch import (BatcherClosed, DynamicBatcher,
                                   Overloaded, QueueFull,
                                   engine_num_vertices)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)


@pytest.fixture(scope="module")
def graph():
    src, dst = uniform_edges(256, 1024, seed=7)
    csr = csr_from_edges(src, dst, 256)
    return csr, build_local_graph(csr, transpose_csr(csr))


@pytest.fixture()
def engine(graph):
    return MultiSourceBFSRunner(graph[1])


# ---------------------------------------------------------------------------
# plane-slot pad/slice helpers (core)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,padded", [(1, 32), (5, 32), (31, 32), (32, 32),
                                      (33, 64), (48, 64), (64, 64)])
def test_pad_plane_slots(b, padded):
    roots = np.arange(1, b + 1, dtype=np.int64)
    slots, orig = bitmap.pad_plane_slots(roots)
    assert orig == b and slots.size == padded and slots.dtype == roots.dtype
    np.testing.assert_array_equal(slots[:b], roots)
    if padded > b:          # pad slots duplicate the first root
        assert (slots[b:] == roots[0]).all()
    rows = np.arange(padded * 3).reshape(padded, 3)
    np.testing.assert_array_equal(bitmap.slice_plane_rows(rows, orig),
                                  rows[:b])


def test_pad_plane_slots_rejects_empty():
    with pytest.raises(ValueError):
        bitmap.pad_plane_slots(np.asarray([], np.int64))


def test_pad_plane_slots_validates_fill():
    roots = np.asarray([4, 9, 2], np.int64)
    with pytest.raises(TypeError):
        bitmap.pad_plane_slots(roots, fill=1.5)
    with pytest.raises(TypeError):
        bitmap.pad_plane_slots(roots, fill=True)    # bool is not a vertex
    with pytest.raises(TypeError):
        bitmap.pad_plane_slots(roots, fill="0")
    with pytest.raises(ValueError):
        bitmap.pad_plane_slots(roots, fill=-1)
    slots, b = bitmap.pad_plane_slots(roots, fill=np.int64(7))
    assert b == 3 and (slots[3:] == 7).all()
    slots, b = bitmap.pad_plane_slots(roots, fill=0)
    assert (slots[3:] == 0).all()
    # full word: fill is validated but unused
    full = np.arange(32, dtype=np.int64)
    slots, b = bitmap.pad_plane_slots(full, fill=5)
    assert b == 32 and slots.size == 32


@pytest.mark.parametrize("b", [1, 31, 33])
def test_pad_slots_inert_in_wave_accounting(graph, engine, b):
    """Pad slots (duplicate planes) must be invisible END TO END: the
    wave's sliced levels equal the per-root oracle, WaveStats counts
    traversed edges over the REAL requests only (a padded B=1 wave must
    not report 32x the edges), and edge traffic matches an unpadded run
    of the same roots (a duplicate plane never changes the union
    frontier)."""
    from repro.core import count_traversed_edges
    csr, g = graph
    roots = np.random.default_rng(100 + b).choice(256, b,
                                                  replace=False).tolist()
    batcher = DynamicBatcher(engine, window=1.0, max_batch=64,
                             clock=FakeClock())
    futures = [batcher.submit(int(r), block=False) for r in roots]
    waves = batcher.flush()
    assert len(waves) == 1
    ws = waves[0]
    assert ws.batch == b and ws.n_slots == ((b + 31) // 32) * 32
    oracle_rows = np.stack([bfs_oracle(csr, int(r)) for r in roots])
    for f, want in zip(futures, oracle_rows):
        np.testing.assert_array_equal(f.result(), want)
    # TEPS numerator over real requests only == slice-then-count
    assert ws.traversed_edges == count_traversed_edges(
        np.asarray(engine.out_deg), oracle_rows)
    # duplicate pad planes leave the union frontier (and so the per-level
    # edge traffic) unchanged: an unpadded engine run inspects the same
    # number of edges
    res = engine.run(np.asarray(roots, np.int64))
    assert ws.edges_inspected == res.edges_inspected
    np.testing.assert_array_equal(
        bitmap.slice_plane_rows(np.vstack([oracle_rows,
                                           oracle_rows[:1].repeat(
                                               ws.n_slots - b, 0)]), b),
        oracle_rows)


@pytest.mark.parametrize("b,slots", [(1, 32), (32, 32), (33, 64)])
def test_padded_slots_never_leak_into_results(graph, engine, b, slots):
    """End-to-end pad/slice round trip through a real wave: B=1, B an
    exact word multiple (no pad at all), and B=33 padded across a word
    boundary.  Every future must equal its per-root oracle and the pad
    slots (duplicates of the first root) must never surface."""
    csr, _ = graph
    batcher = DynamicBatcher(engine, window=1.0, max_batch=64,
                             clock=FakeClock())
    roots = [int(r) for r in
             np.random.default_rng(b).choice(256, b, replace=False)]
    futures = [batcher.submit(r, block=False) for r in roots]
    waves = batcher.flush()
    assert len(waves) == 1
    wave = waves[0]
    assert wave.batch == b and wave.n_slots == slots
    for f, r in zip(futures, roots):
        lv = np.asarray(f.result(timeout=0), np.int64)
        assert lv.shape == (256,)           # one row per vertex, no slots
        np.testing.assert_array_equal(lv, bfs_oracle(csr, r))
    assert batcher.stats()["requests"] == b
    batcher.close()


# ---------------------------------------------------------------------------
# deterministic fake-clock scheduling
# ---------------------------------------------------------------------------

def test_one_window_is_exactly_one_wave_matching_oracle(graph, engine):
    """Acceptance: N submits inside one window -> ONE MS-BFS wave; every
    future's levels equal the per-root oracle."""
    csr, _ = graph
    clock = FakeClock()
    b = DynamicBatcher(engine, window=0.01, max_batch=32, clock=clock)
    roots = [0, 3, 17, 42, 199]
    futures = []
    for r in roots:
        futures.append(b.submit(r, block=False))
        clock.advance(0.001)            # arrivals spread inside the window
    assert b.pump() is None             # window not elapsed -> nothing due
    assert not any(f.done() for f in futures)
    clock.advance(0.01)                 # oldest request now past the window
    wave = b.pump()
    assert wave is not None and b.pump() is None
    assert len(b.waves) == 1 and wave.batch == len(roots)
    assert wave.n_slots == 32           # padded to one full plane word
    for f, r in zip(futures, roots):
        assert f.done() and f.wave is wave
        np.testing.assert_array_equal(np.asarray(f.result(), np.int64),
                                      bfs_oracle(csr, r))
    # latency is deterministic under the fake clock: submit -> wave cut
    assert futures[0].latency == pytest.approx(0.015)
    assert futures[-1].latency == pytest.approx(0.011)
    s = b.stats()
    assert s["waves"] == 1 and s["requests"] == 5
    assert s["traversed_edges"] == wave.traversed_edges > 0


def test_full_wave_dispatches_before_window(engine):
    clock = FakeClock()
    b = DynamicBatcher(engine, window=10.0, max_batch=4, clock=clock,
                       pad_to_plane=False)
    for r in range(7):
        b.submit(r, block=False)
    wave = b.pump()                     # cap reached: no deadline needed
    assert wave.batch == 4 and wave.n_slots == 4
    assert b.pump() is None             # 3 left, window wide open
    waves = b.flush()
    assert len(waves) == 1 and waves[0].batch == 3
    assert [w.wave_id for w in b.waves] == [0, 1]


def test_window_restarts_from_oldest_remaining(engine):
    clock = FakeClock()
    b = DynamicBatcher(engine, window=1.0, max_batch=2, clock=clock)
    b.submit(1, block=False)
    clock.advance(0.5)
    b.submit(2, block=False)
    b.submit(3, block=False)            # full wave of 2 is now due
    assert b.pump().batch == 2
    assert b.pump() is None             # root 3 aged only 0.0 of its window
    clock.advance(0.99)
    assert b.pump() is None             # 0.99 < 1.0: still waiting
    clock.advance(0.02)
    assert b.pump().batch == 1


def test_backpressure_bounded_queue(engine):
    b = DynamicBatcher(engine, window=1.0, max_pending=3, clock=FakeClock())
    for r in range(3):
        b.submit(r, block=False)
    with pytest.raises(QueueFull):
        b.submit(3, block=False)
    # manual mode never drains concurrently: block=True must also raise
    with pytest.raises(QueueFull):
        b.submit(3)
    b.flush()
    b.submit(3, block=False)            # capacity freed by the wave cut
    b.close(drain=True)


def test_close_drains_or_cancels(graph, engine):
    csr, _ = graph
    b = DynamicBatcher(engine, window=5.0, clock=FakeClock())
    f = b.submit(9, block=False)
    b.close(drain=True)                 # flushes despite the open window
    np.testing.assert_array_equal(np.asarray(f.result(timeout=0), np.int64),
                                  bfs_oracle(csr, 9))
    with pytest.raises(BatcherClosed):
        b.submit(1, block=False)

    b2 = DynamicBatcher(engine, window=5.0, clock=FakeClock())
    f2 = b2.submit(9, block=False)
    b2.close(drain=False)               # cancel instead of serving
    assert f2.done()
    with pytest.raises(BatcherClosed):
        f2.result(timeout=0)
    assert b2.stats()["waves"] == 0


def test_submit_validates_roots(engine):
    b = DynamicBatcher(engine, clock=FakeClock())
    assert engine_num_vertices(engine) == 256
    with pytest.raises(ValueError):
        b.submit(-1, block=False)
    with pytest.raises(ValueError):
        b.submit(256, block=False)
    with pytest.raises(ValueError, match="integer"):
        b.submit(5.7, block=False)      # truncation would serve root 5
    b.close()


def test_duplicate_roots_resolve_independently(graph, engine):
    csr, _ = graph
    b = DynamicBatcher(engine, clock=FakeClock())
    f1 = b.submit(5, block=False)
    f2 = b.submit(5, block=False)
    b.flush()
    want = bfs_oracle(csr, 5)
    for f in (f1, f2):
        np.testing.assert_array_equal(np.asarray(f.result(), np.int64),
                                      want)


def test_wrapper_engine_bad_root_fails_only_its_future(graph, engine):
    """An opaque wrapper engine (no .g/.pg) defeats submit-time validation;
    a bad root rejected at dispatch must not fail its co-batched wave."""
    csr, _ = graph

    class Wrapper:
        def __init__(self, inner):
            self._inner = inner

        def run_batch(self, roots):
            return self._inner.run(np.asarray(roots)).levels

    b = DynamicBatcher(Wrapper(engine), window=1.0, clock=FakeClock())
    assert b.num_vertices is None and b.out_deg is None
    good = b.submit(3, block=False)
    bad = b.submit(999, block=False)       # accepted: |V| unknown here
    good2 = b.submit(7, block=False)
    b.flush()
    with pytest.raises(ValueError):
        bad.result(timeout=0)
    for f, r in ((good, 3), (good2, 7)):
        np.testing.assert_array_equal(np.asarray(f.result(), np.int64),
                                      bfs_oracle(csr, r))
    s = b.stats()
    assert s["errors"] >= 1
    assert "aggregate_teps" not in s       # no out_deg -> TEPS unknowable
    b.close()


# ---------------------------------------------------------------------------
# threaded real-clock mode + distributed engine
# ---------------------------------------------------------------------------

def test_threaded_serving_matches_oracle(graph, engine):
    csr, _ = graph
    roots = [2, 50, 100, 150, 200, 250]
    with DynamicBatcher(engine, window=0.05) as b:
        futures = [b.submit(r) for r in roots]
        levels = [f.result(timeout=120.0) for f in futures]
    for lv, r in zip(levels, roots):
        np.testing.assert_array_equal(np.asarray(lv, np.int64),
                                      bfs_oracle(csr, r))
    s = b.stats()
    assert 1 <= s["waves"] <= len(roots) and s["requests"] == len(roots)
    assert s["latency_p99"] >= s["latency_p50"] > 0


def test_distributed_engine_behind_batcher():
    src, dst = uniform_edges(64, 256, seed=3)
    csr = csr_from_edges(src, dst, 64)
    pg = partition_graph(csr, transpose_csr(csr), 4)
    mesh = make_mesh((1,), ("data",))
    eng = DistributedBFS(pg, mesh, cfg=DistConfig(dispatch="bitmap"))
    deg = np.diff(csr.indptr)
    b = DynamicBatcher(eng, out_deg=deg, window=0.01, clock=FakeClock())
    roots = [0, 13, 63]
    futures = [b.submit(r, block=False) for r in roots]
    waves = b.flush()
    assert len(waves) == 1 and waves[0].n_slots == 32
    assert waves[0].traversed_edges > 0
    for f, r in zip(futures, roots):
        np.testing.assert_array_equal(np.asarray(f.result(), np.int64),
                                      bfs_oracle(csr, r))
    b.close()


# ---------------------------------------------------------------------------
# fault tolerance: typed futures, drain under failure, supervised waves
# ---------------------------------------------------------------------------

class AlwaysDown:
    """Transiently-failing engine (every wave raises RuntimeError)."""

    last_stats = {}

    def run_batch(self, roots):
        raise RuntimeError("engine down")


def test_future_done_and_exception_accessors(graph, engine):
    csr, _ = graph
    b = DynamicBatcher(engine, window=1.0, clock=FakeClock())
    f = b.submit(5, block=False)
    assert not f.done()
    assert f.exception() is None            # pending: poll returns None
    assert f.exception(timeout=0.01) is None
    b.flush()
    assert f.done() and f.exception() is None       # success: still None
    np.testing.assert_array_equal(np.asarray(f.result(), np.int64),
                                  bfs_oracle(csr, 5))
    b.close()


def test_failed_future_raises_typed_error_immediately():
    """A resolved-with-error future must raise at once, not ride out the
    caller's timeout (the old bug: error-resolution didn't set the event,
    so result(timeout=30) blocked the full 30s)."""
    import time as _time

    b = DynamicBatcher(AlwaysDown(), window=1.0, clock=FakeClock())
    f = b.submit(3, block=False)
    b.flush()
    assert f.done()
    assert isinstance(f.exception(), RuntimeError)
    t0 = _time.perf_counter()
    with pytest.raises(RuntimeError):
        f.result(timeout=30.0)
    assert _time.perf_counter() - t0 < 5.0
    b.close()


def test_drain_resolves_every_future_with_failing_engine_legacy():
    """close(drain=True) with a permanently failing engine must terminate
    and resolve EVERY future with a typed error (no unbounded retry)."""
    b = DynamicBatcher(AlwaysDown(), window=1.0, clock=FakeClock())
    futures = [b.submit(r, block=False) for r in range(5)]
    b.close(drain=True)
    for f in futures:
        assert f.done()
        assert isinstance(f.exception(), RuntimeError)
    s = b.stats()
    assert s["errors"] >= 1 and s["requests"] == 0


def test_drain_resolves_every_future_with_failing_engine_supervised():
    from repro.ft import EngineSupervisor, WaveAbandoned

    sup = EngineSupervisor(AlwaysDown(), max_retries=1, backoff=0.0,
                           watchdog=False)
    b = DynamicBatcher(sup, window=1.0, clock=FakeClock())
    futures = [b.submit(r, block=False) for r in range(4)]
    b.close(drain=True)
    for f in futures:
        assert f.done()
        assert isinstance(f.exception(), WaveAbandoned)
    s = b.stats()
    assert s["requests_failed"] == 4
    assert s["fault_tolerance"]["retries"] == 1


def test_legacy_deterministic_fault_retries_singletons_once(graph, engine):
    """Unsupervised dispatch splits a deterministically-failing wave into
    singleton retries EXACTLY once — a singleton that still fails resolves
    with its error instead of re-enqueueing forever."""
    csr, _ = graph

    class BadRootEngine:
        last_stats = {}

        def __init__(self, inner):
            self._inner = inner

        def run_batch(self, roots):
            if 999 in np.asarray(roots).tolist():
                raise ValueError("root out of range")
            return self._inner.run(np.asarray(roots)).levels

    b = DynamicBatcher(BadRootEngine(engine), window=1.0, clock=FakeClock())
    good = b.submit(3, block=False)
    bad = b.submit(999, block=False)
    b.close(drain=True)                     # wave + singleton retries
    assert good.done() and bad.done()
    with pytest.raises(ValueError):
        bad.result(timeout=0)
    np.testing.assert_array_equal(np.asarray(good.result(), np.int64),
                                  bfs_oracle(csr, 3))


def test_supervised_wave_quarantines_poison_and_serves_rest(graph, engine):
    """EngineSupervisor behind the batcher: per-request outcomes — the
    poisoned root fails typed, co-batched requests get correct levels."""
    from repro.ft import (EngineSupervisor, FaultyEngine, PoisonedRoot,
                          RequestQuarantined)

    csr, _ = graph
    sup = EngineSupervisor(FaultyEngine(engine, poisoned_roots=[42]),
                           backoff=0.0, watchdog=False)
    b = DynamicBatcher(sup, out_deg=np.asarray(engine.out_deg),
                       window=1.0, clock=FakeClock())
    roots = [0, 3, 42, 17, 99]
    futures = [b.submit(r, block=False) for r in roots]
    waves = b.flush()
    assert len(waves) == 1
    ws = waves[0]
    assert ws.failed == 1 and ws.quarantined == [42]
    assert ws.traversals > 1                # bisection sub-waves counted
    for f, r in zip(futures, roots):
        if r == 42:
            exc = f.exception()
            assert isinstance(exc, RequestQuarantined)
            assert isinstance(exc.__cause__, PoisonedRoot)
        else:
            np.testing.assert_array_equal(np.asarray(f.result(), np.int64),
                                          bfs_oracle(csr, r))
    s = b.stats()
    assert s["requests"] == 4 and s["requests_failed"] == 1
    assert s["fault_tolerance"]["quarantined"] == [42]
    assert s["traversed_edges"] > 0         # TEPS over the served four
    b.close()


# ---------------------------------------------------------------------------
# multi-word waves (max_batch spanning several plane words)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,slots", [(33, 64), (64, 64), (96, 96)])
def test_multiword_wave_pads_and_slices_without_leaks(graph, engine, b,
                                                      slots):
    """A wave wider than one plane word: pad slots must not inflate the
    wave's TEPS numerator and must never leak into any future's row."""
    from repro.core import count_traversed_edges
    csr, _ = graph
    batcher = DynamicBatcher(engine, window=1.0, max_batch=96,
                             clock=FakeClock())
    rng = np.random.default_rng(1000 + b)
    roots = [int(r) for r in rng.choice(256, b, replace=(b > 256))]
    futures = [batcher.submit(r, block=False) for r in roots]
    waves = batcher.flush()
    assert len(waves) == 1
    ws = waves[0]
    assert ws.batch == b and ws.n_slots == slots
    oracle_rows = np.stack([bfs_oracle(csr, r) for r in roots])
    for f, want in zip(futures, oracle_rows):
        lv = np.asarray(f.result(timeout=0), np.int64)
        assert lv.shape == (256,)
        np.testing.assert_array_equal(lv, want)
    # TEPS numerator over the REAL requests only, not the padded slots
    assert ws.traversed_edges == count_traversed_edges(
        np.asarray(engine.out_deg), oracle_rows)
    assert batcher.stats()["requests"] == b
    batcher.close()


def test_supervised_multiword_bisection_keeps_future_order(graph, engine):
    """Futures <-> outcomes ordering through a supervised MULTI-WORD wave
    that bisects: with a poison mid-wave at B=64, every clean future must
    resolve with ITS OWN root's levels (bisection reorders sub-waves
    internally; the mapping back to futures must not)."""
    from repro.ft import EngineSupervisor, FaultyEngine, RequestQuarantined

    csr, _ = graph
    sup = EngineSupervisor(FaultyEngine(engine, poisoned_roots=[42]),
                           backoff=0.0, watchdog=False)
    b = DynamicBatcher(sup, out_deg=np.asarray(engine.out_deg),
                       window=1.0, max_batch=96, clock=FakeClock())
    roots = list(range(64))                 # includes poison root 42
    futures = [b.submit(r, block=False) for r in roots]
    waves = b.flush()
    assert len(waves) == 1
    ws = waves[0]
    assert ws.batch == 64 and ws.n_slots == 64
    assert ws.failed == 1 and ws.quarantined == [42]
    for f, r in zip(futures, roots):
        if r == 42:
            assert isinstance(f.exception(), RequestQuarantined)
        else:
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=0), np.int64),
                bfs_oracle(csr, r))
    b.close()


# ---------------------------------------------------------------------------
# accounting bugfix regressions (SLO-blind percentiles, injected-clock
# timeout, busy-seconds undercount)
# ---------------------------------------------------------------------------

def test_failed_wave_latencies_reach_percentiles_legacy():
    """Regression: the legacy failure path never populated ws.latencies
    and stats() filtered failed waves out, so p99 under faults excluded
    exactly the requests that blew the SLO."""
    clock = FakeClock()
    b = DynamicBatcher(AlwaysDown(), window=1.0, clock=clock)
    futures = [b.submit(r, block=False) for r in range(3)]
    clock.advance(2.0)                      # requests age before the wave
    b.flush()
    for f in futures:
        assert f.done() and f.latency == pytest.approx(2.0)
        assert f.wave is not None
    s = b.stats()
    assert s["errors"] == 1
    assert s["latency_p99"] == pytest.approx(2.0)
    assert s["latency_p50"] == pytest.approx(2.0)
    b.close()


def test_failed_wave_latencies_reach_percentiles_supervised():
    """Regression for the supervised path: ws.latencies were populated
    but stats() dropped any wave with error set before pooling."""
    from repro.ft import EngineSupervisor

    clock = FakeClock()
    sup = EngineSupervisor(AlwaysDown(), max_retries=0, backoff=0.0,
                           watchdog=False)
    b = DynamicBatcher(sup, window=1.0, clock=clock)
    futures = [b.submit(r, block=False) for r in range(4)]
    clock.advance(3.0)
    b.flush()
    assert all(f.done() for f in futures)
    s = b.stats()
    assert s["requests_failed"] == 4
    assert s["latency_p99"] == pytest.approx(3.0)
    b.close()


def test_submit_timeout_runs_on_injected_clock(engine):
    """Regression: submit(block=True, timeout=) used raw time.monotonic
    for its deadline, so a fake-clock batcher with a worker thread had
    undefined timeout semantics.  Advancing the FAKE clock past the
    timeout must raise QueueFull promptly (wall time barely moves)."""
    import threading as _threading
    import time as _time

    clock = FakeClock()
    b = DynamicBatcher(engine, window=1e6, max_pending=1, clock=clock,
                       start=True)         # worker thread + fake clock
    b.submit(0, block=False)               # queue now at capacity

    def expire():
        _time.sleep(0.3)
        clock.advance(10.0)                # past t_submit + timeout
        with b._cond:
            b._cond.notify_all()

    t = _threading.Thread(target=expire, daemon=True)
    t.start()
    t0 = _time.perf_counter()
    with pytest.raises(QueueFull):
        b.submit(1, timeout=5.0)           # 5 FAKE seconds, not wall
    assert _time.perf_counter() - t0 < 4.0
    t.join()
    b.close(drain=True)


def test_busy_seconds_accrue_for_failed_waves(graph):
    """Regression: _record skipped busy-seconds for error waves, so
    lifetime aggregate TEPS was inflated under chaos (edges / too-small
    denominator)."""
    import time as _time

    class SlowDown:
        last_stats = {}

        def run_batch(self, roots):
            _time.sleep(0.02)              # burn real engine time
            raise RuntimeError("engine down")

    b = DynamicBatcher(SlowDown(), out_deg=np.ones(256, np.int64),
                       window=1.0, clock=FakeClock())
    for r in range(3):
        b.submit(r, block=False)
    b.flush()
    s = b.stats()
    assert s["errors"] == 1
    assert s["busy_seconds"] >= 0.02       # the failed wave's engine time
    assert s["busy_seconds"] == pytest.approx(
        sum(w.seconds for w in b.waves), abs=1e-4)   # stats() rounds
    assert s["aggregate_teps"] == 0.0      # 0 edges / REAL busy time
    b.close()


# ---------------------------------------------------------------------------
# SLO-aware cutting: deadlines, priorities, preemption, miss accounting
# ---------------------------------------------------------------------------

def test_submit_rejects_nonpositive_deadline(engine):
    b = DynamicBatcher(engine, clock=FakeClock())
    with pytest.raises(ValueError):
        b.submit(1, block=False, deadline=0.0)
    with pytest.raises(ValueError):
        b.submit(1, block=False, deadline=-1.0)
    b.close(drain=False)


def test_deadline_preempts_window(graph, engine):
    """An urgent request must cut the wave EARLY: before its deadline
    minus the margin, not at the (much later) window expiry."""
    csr, _ = graph
    clock = FakeClock()
    b = DynamicBatcher(engine, window=10.0, max_batch=32, clock=clock,
                       slo_margin=0.5)
    f = b.submit(5, block=False, deadline=1.0)
    assert b.pump() is None                 # 0 < 1.0 - 0.5: not yet
    clock.advance(0.6)                      # past deadline - margin
    ws = b.pump()
    assert ws is not None and ws.preempted
    assert ws.deadline_requests == 1 and ws.slo_misses == 0
    assert f.slo_miss is False              # resolved at t=0.6 < 1.0
    np.testing.assert_array_equal(np.asarray(f.result(), np.int64),
                                  bfs_oracle(csr, 5))
    s = b.stats()
    assert s["slo_requests"] == 1 and s["slo_miss_rate"] == 0.0
    b.close()


def test_late_resolution_counts_as_slo_miss(engine):
    clock = FakeClock()
    b = DynamicBatcher(engine, window=0.1, clock=clock, slo_margin=0.0)
    f = b.submit(5, block=False, deadline=0.5)
    clock.advance(1.0)                      # deadline already blown
    ws = b.pump()
    assert ws.deadline_requests == 1 and ws.slo_misses == 1
    assert f.slo_miss is True
    assert f.done() and f.exception() is None   # late but correct
    s = b.stats()
    assert s["slo_misses"] == 1 and s["slo_miss_rate"] == 1.0
    b.close()


def test_failed_request_with_deadline_is_a_miss(engine):
    """A typed failure inside the SLO window is still a miss — the
    client did not get the answer it asked for in time."""
    b = DynamicBatcher(AlwaysDown(), window=1.0, clock=FakeClock())
    f = b.submit(3, block=False, deadline=100.0)
    b.flush()
    assert isinstance(f.exception(), RuntimeError)
    assert f.slo_miss is True
    s = b.stats()
    assert s["slo_requests"] == 1 and s["slo_miss_rate"] == 1.0
    b.close()


def test_wave_cut_orders_by_priority_then_deadline(graph, engine):
    """Urgency-first cutting: priority tier first, oldest deadline next,
    arrival order last — a late urgent request still makes the wave."""
    csr, _ = graph
    clock = FakeClock()
    b = DynamicBatcher(engine, window=100.0, max_batch=2, clock=clock)
    f_plain = b.submit(1, block=False)                   # no SLO
    f_loose = b.submit(2, block=False, deadline=5.0)
    f_tight = b.submit(3, block=False, deadline=1.0)     # latest arrival
    ws = b.pump()                           # full wave (max_batch=2)
    assert ws.batch == 2
    # the two deadline carriers ran; the plain request waits
    assert f_tight.done() and f_loose.done() and not f_plain.done()
    b.flush()
    for f, r in ((f_plain, 1), (f_loose, 2), (f_tight, 3)):
        np.testing.assert_array_equal(np.asarray(f.result(), np.int64),
                                      bfs_oracle(csr, r))
    b.close()


def test_priority_beats_deadline_in_cut_order(engine):
    clock = FakeClock()
    b = DynamicBatcher(engine, window=100.0, max_batch=1, clock=clock)
    f_dl = b.submit(1, block=False, deadline=0.5)
    f_hi = b.submit(2, block=False, priority=-1)
    ws = b.pump()                           # full (max_batch=1)
    assert ws.batch == 1
    assert f_hi.done() and not f_dl.done()  # priority tier wins
    b.close(drain=True)


# ---------------------------------------------------------------------------
# pipelined mode (cutter / dispatcher / finisher stages)
# ---------------------------------------------------------------------------

def test_pipeline_requires_threaded_mode(engine):
    with pytest.raises(ValueError):
        DynamicBatcher(engine, clock=FakeClock(), pipeline=True)


def test_pipelined_serving_matches_oracle(graph, engine):
    """Real-clock pipelined mode: the three stages hand off through
    queues and every future still matches its per-root oracle."""
    csr, _ = graph
    roots = [2, 50, 100, 150, 200, 250, 33, 77]
    with DynamicBatcher(engine, window=0.02, max_batch=64,
                        pipeline=True) as b:
        futures = [b.submit(r) for r in roots]
        levels = [f.result(timeout=120.0) for f in futures]
    for lv, r in zip(levels, roots):
        np.testing.assert_array_equal(np.asarray(lv, np.int64),
                                      bfs_oracle(csr, r))
    s = b.stats()
    assert s["pipeline"] is True
    assert s["requests"] == len(roots)
    assert s["engine_idle_seconds"] >= 0.0
    assert s["latency_p99"] >= s["latency_p50"]


def test_pipelined_supervised_chaos_resolves_everything(graph, engine):
    """Pipelined batcher over a supervised faulty engine: typed errors
    still resolve through the finisher stage, nothing hangs."""
    from repro.ft import EngineSupervisor, FaultyEngine, RequestQuarantined

    csr, _ = graph
    sup = EngineSupervisor(FaultyEngine(engine, poisoned_roots=[42]),
                           backoff=0.0, watchdog=False)
    with DynamicBatcher(sup, out_deg=np.asarray(engine.out_deg),
                        window=0.02, max_batch=64, pipeline=True) as b:
        futures = [b.submit(r) for r in [3, 42, 17, 99]]
        for f in futures:
            f.exception(timeout=120.0)      # wait for resolution
    for f, r in zip(futures, [3, 42, 17, 99]):
        if r == 42:
            assert isinstance(f.exception(), RequestQuarantined)
        else:
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=0), np.int64),
                bfs_oracle(csr, r))
    assert b.stats()["requests_failed"] == 1


# ---------------------------------------------------------------------------
# Admission control (shed), health streaks, pool-support plumbing
# ---------------------------------------------------------------------------

class TimedEngine:
    """Wraps a runner, charging a fixed fake-clock cost per wave so the
    batcher's EWMA service estimate is deterministic."""

    def __init__(self, inner, clock, cost=0.2, fails_left=0):
        self.inner = inner
        self.clock = clock
        self.cost = float(cost)
        self.fails_left = int(fails_left)
        self.num_vertices = inner.num_vertices

    def run_batch(self, roots, **kw):
        self.clock.advance(self.cost)
        if self.fails_left > 0:
            self.fails_left -= 1
            raise RuntimeError("injected engine failure")
        return self.inner.run_batch(roots, **kw)


def test_service_hint_primes_estimated_delay(engine):
    clock = FakeClock()
    b = DynamicBatcher(engine, window=1.0, max_batch=4, clock=clock,
                       service_hint=1.0)
    assert b.estimated_delay() == pytest.approx(1.0)    # idle: one wave
    b.submit(3, block=False)
    b.submit(5, block=False)
    assert b.estimated_delay() == pytest.approx(1.5)    # 1.0 x (1 + 2/4)
    b.flush()
    b.close()
    with pytest.raises(ValueError):
        DynamicBatcher(engine, clock=FakeClock(), service_hint=-0.5)


def test_ewma_tracks_measured_wave_service(graph, engine):
    clock = FakeClock()
    timed = TimedEngine(engine, clock, cost=0.2)
    b = DynamicBatcher(timed, window=1.0, clock=clock)
    assert b.estimated_delay() == 0.0       # unprimed: never sheds cold
    b.submit(3, block=False)
    b.flush()
    assert b.estimated_delay() == pytest.approx(0.2)    # first wave primes
    b.close()


def test_shed_rejects_doomed_deadline_with_typed_overloaded(graph, engine):
    """Admission control: a deadline the backlog already dooms is refused
    up front so it fails in microseconds, not after a full queue wait."""
    clock = FakeClock()
    b = DynamicBatcher(engine, window=1.0, max_batch=4, clock=clock,
                       shed=True, service_hint=1.0)
    ok = b.submit(3, block=False, deadline=10.0)        # 1.0s est <= 10s
    with pytest.raises(Overloaded):
        b.submit(5, block=False, deadline=0.4)          # 1.25s est > 0.4s
    b.submit(7, block=False)                # no deadline: never shed
    b.flush()
    assert ok.exception() is None
    s = b.stats()
    assert s["shed"] == 1 and s["requests"] == 2
    b.close()


def test_shed_off_queues_doomed_deadline(engine):
    b = DynamicBatcher(engine, window=1.0, clock=FakeClock(),
                       service_hint=5.0)   # shed=False (default)
    f = b.submit(3, block=False, deadline=0.01)
    b.flush()
    assert f.done() and "shed" not in b.stats()
    b.close()


def test_cancel_pending_pops_without_resolving(graph, engine):
    csr, _ = graph
    b = DynamicBatcher(engine, window=1.0, clock=FakeClock())
    futs = [b.submit(r, block=False, deadline=5.0) for r in (3, 5, 9)]
    popped = b.cancel_pending()
    assert popped == futs and b.backlog() == 0
    assert not any(f.done() for f in popped)
    assert b.flush() == []                  # queue really is empty
    # the pool's redispatch path: transplant onto another batcher with
    # submit-time deadline/clock state intact
    b2 = DynamicBatcher(engine, window=1.0, clock=FakeClock())
    for f in popped:
        b2._submit_future(f)
    b2.flush()
    for f, r in zip(popped, (3, 5, 9)):
        assert f.t_deadline == 5.0
        np.testing.assert_array_equal(np.asarray(f.result(), np.int64),
                                      bfs_oracle(csr, r))
    b.close()
    b2.close()


def test_submit_future_respects_capacity_and_close(engine):
    b = DynamicBatcher(engine, window=1.0, max_pending=1,
                       clock=FakeClock())
    f = b.submit(3, block=False)
    b.cancel_pending()
    b.submit(5, block=False)
    with pytest.raises(QueueFull):
        b._submit_future(f)
    b.flush()
    b.close()
    with pytest.raises(BatcherClosed):
        b._submit_future(f)


def test_consecutive_failures_streak_resets_on_success(graph, engine):
    clock = FakeClock()
    timed = TimedEngine(engine, clock, fails_left=2)
    b = DynamicBatcher(timed, window=1.0, clock=clock)
    for want in (1, 2):
        b.submit(3, block=False)
        b.flush()
        assert b.consecutive_failures == want
    assert b.stats()["consecutive_failures"] == 2
    b.submit(3, block=False)                # engine healthy again
    b.flush()
    assert b.consecutive_failures == 0
    assert "consecutive_failures" not in b.stats()
    b.close()


def test_failure_handler_takes_ownership_of_failing_futures(graph, engine):
    """A True-returning handler owns the future: the batcher neither
    resolves nor books it, and the streak still advances (the pool's
    eviction signal must see every engine failure)."""
    clock = FakeClock()
    handled = []

    def handler(fut, exc):
        handled.append((fut, exc))
        return len(handled) == 1            # own the first, decline later

    timed = TimedEngine(engine, clock, fails_left=2)
    b = DynamicBatcher(timed, window=1.0, clock=clock,
                       failure_handler=handler)
    f1 = b.submit(3, block=False)
    b.flush()
    assert not f1.done()                    # handed off, not resolved
    f2 = b.submit(5, block=False)
    b.flush()
    assert f2.done()                        # handler declined: fails here
    assert isinstance(f2.exception(), RuntimeError)
    assert [f for f, _ in handled] == [f1, f2]
    assert b.consecutive_failures == 2
    assert b.stats()["requests_failed"] == 1    # only the declined one
    f1._fail(RuntimeError("resolved by the test, standing in for a pool"))
    b.close()


def test_failure_handler_exception_is_contained(graph, engine):
    clock = FakeClock()
    timed = TimedEngine(engine, clock, fails_left=1)
    b = DynamicBatcher(timed, window=1.0, clock=clock,
                       failure_handler=lambda f, e: 1 / 0)
    f = b.submit(3, block=False)
    b.flush()                               # handler blew up: treat as False
    assert f.done() and isinstance(f.exception(), RuntimeError)
    b.close()
