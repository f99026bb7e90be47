"""Host spans and per-level counters (``repro.spans``).

Under ``jax.profiler.start_trace`` one ``VertexProgramRunner`` wave and one
threaded ``DynamicBatcher`` wave on a small RMAT graph must write every
span the readers key on, with their args, read back through
``ProfileData``; the spans nest on their thread (``dynbatch.execute`` ⊃
``vp.wave`` ⊃ ``vp.init``/``vp.sync``/``vp.level.host``/``vp.rows``);
``last_stats["levels"]`` holds one record per level that agrees with the
wave's own counts; and the answers are the same with the profiler on and
off.

The records also pin the per-level edge budget: each budgeted level runs
at ``max(floor, next_pow2(need))``, the floor being the wave's starting
budget (``init_budget`` or the ``run_batch(budget=)`` override, capped at
the edge count plus one).  On a small Kronecker graph a 32-root wave goes
push (wide) -> pull -> push (narrow), so the tail push level runs below
the wave's largest rung; a wave over roots with no arcs runs its one push
level at exactly the override (the bench's warm-up relies on it); an
overflowed level re-runs at twice its rung, and only that level does.
"""
import gc

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.core.vertex_program as vp
from repro.core import (ConnectedComponentsRunner, MultiSourceBFSRunner,
                        SSSPRunner, bfs_oracle, build_local_graph,
                        msbfs_reference)
from repro.core.bfs_local import SV_OVERFLOW, owner_path
from repro.graph import (csr_from_edges, rmat_edges, symmetrize_csr,
                         transpose_csr)
from repro.launch.dynbatch import DynamicBatcher
from repro.spans import gc_spans

ROOTS = np.array([0, 3, 17, 40, 99, 200, 251], np.int64)
PREFIXES = ("vp.", "dynbatch.", "host.")


@pytest.fixture(scope="module")
def graph():
    src, dst = rmat_edges(8, 8, seed=5)
    csr = csr_from_edges(src, dst, 256)
    return csr, build_local_graph(csr, transpose_csr(csr))


def overflow_once(monkeypatch):
    """Make the first push step report a budget overflow, so the level
    loop re-runs that level at twice the budget."""
    real, fired = vp.vp_push_step, []

    def step(*args, **kw):
        out = real(*args, **kw)
        if fired:
            return out
        fired.append(True)
        return (*out[:3], out[3].at[SV_OVERFLOW].set(1))

    monkeypatch.setattr(vp, "vp_push_step", step)


def read_spans(trace_dir) -> list[dict]:
    """Host events of the program, one dict each, with the index of the
    line (thread) that wrote them."""
    paths = sorted(trace_dir.glob("**/*.xplane.pb"))
    assert paths, "the profiler wrote no trace"
    out = []
    for plane in ProfileData.from_file(str(paths[-1])).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    s = float(ev.start_ns)
                    out.append(dict(name=ev.name, line=i, start=s,
                                    end=s + float(ev.duration_ns),
                                    args=dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(graph, tmp_path_factory):
    """A runner wave whose first push level overflows once, a plain runner
    wave, a batcher wave and a collection, all under the profiler."""
    trace_dir = tmp_path_factory.mktemp("trace")
    csr, g = graph
    runner = MultiSourceBFSRunner(g)
    batcher_engine = MultiSourceBFSRunner(g)
    runner.run_batch(ROOTS)                 # compile outside the trace
    mp = pytest.MonkeyPatch()
    overflow_once(mp)
    jax.profiler.start_trace(str(trace_dir))
    try:
        runner.run_batch(ROOTS)
        mp.undo()
        stats = dict(runner.last_stats)
        rows = runner.run_batch(ROOTS)
        plain = dict(runner.last_stats)
        with DynamicBatcher(batcher_engine, window=0.05,
                            max_batch=32) as b:
            futures = [b.submit(int(r)) for r in ROOTS]
            served = [f.result(timeout=120.0) for f in futures]
        gc.collect()
    finally:
        mp.undo()
        jax.profiler.stop_trace()
    return dict(spans=read_spans(trace_dir), rows=rows, stats=stats,
                plain=plain,
                futures=futures, served=served, csr=csr, g=g)


EXPECTED_ARGS = {
    "vp.wave": {"slots", "budget"},
    "vp.init": set(),
    "vp.sync": {"level", "retry"},
    "vp.level.host": {"level", "retry", "mode", "budget", "owner"},
    "vp.rows": {"slots"},
    "dynbatch.submit": {"req"},
    "dynbatch.cut": {"wave", "batch", "preempted"},
    "dynbatch.prepare": {"wave"},
    "dynbatch.execute": {"wave"},
    "dynbatch.finish": {"wave"},
    "host.gc": {"generation", "collected"},
}


@pytest.mark.parametrize("name", sorted(EXPECTED_ARGS))
def test_span_is_written_with_its_args(traced, name):
    got = [s for s in traced["spans"] if s["name"] == name]
    assert got, f"no {name} span in the trace"
    assert any(set(s["args"]) == EXPECTED_ARGS[name] for s in got), \
        (name, [s["args"] for s in got])


def _runner_spans(traced, name):
    """Spans of the runner's own wave: the first ``vp.wave`` and what lies
    inside it."""
    waves = sorted((s for s in traced["spans"] if s["name"] == "vp.wave"),
                   key=lambda s: s["start"])
    w = waves[0]
    return [s for s in traced["spans"] if s["name"] == name
            and s["line"] == w["line"] and w["start"] <= s["start"]
            and s["end"] <= w["end"]]


def test_one_level_host_span_per_level_plus_retries(traced):
    st = traced["stats"]
    assert st["overflow_retries"] == 1
    host = _runner_spans(traced, "vp.level.host")
    assert len(host) == st["iterations"] + st["overflow_retries"]
    got = sorted((s["args"]["level"], s["args"]["retry"]) for s in host)
    want = sorted((lvl, r) for lvl, rec in enumerate(st["levels"])
                  for r in range(rec["retries"] + 1))
    assert got == want
    for s in host:
        rec = st["levels"][s["args"]["level"]]
        assert s["args"]["mode"] == rec["mode"]
        # the owner path of the rung this dispatch ran (a re-run's own)
        assert s["args"].get("owner") == (
            owner_path(traced["g"].n_pad, s["args"]["budget"])
            if rec["mode"] == "push" else None)
    syncs = _runner_spans(traced, "vp.sync")
    # the init's statvec, one per level, one per re-run
    assert len(syncs) == 1 + len(host)


@pytest.mark.parametrize("outer,inner", [
    ("dynbatch.execute", "vp.wave"), ("vp.wave", "vp.init"),
    ("vp.wave", "vp.sync"), ("vp.wave", "vp.level.host"),
    ("vp.wave", "vp.rows")])
def test_spans_nest_on_their_thread(traced, outer, inner):
    spans = traced["spans"]
    outers = [s for s in spans if s["name"] == outer]
    inners = [s for s in spans if s["name"] == inner]
    assert outers and inners
    for s in inners:
        if outer == "dynbatch.execute" and s["line"] not in {
                o["line"] for o in outers}:
            continue        # the runner's own wave, called directly
        assert any(o["line"] == s["line"] and o["start"] <= s["start"]
                   and s["end"] <= o["end"] for o in outers), (outer, s)


def test_wave_and_request_ids_join(traced):
    futures = traced["futures"]
    seq = futures[0].wave.seq
    assert all(f.wave.seq == seq for f in futures)
    for name in ("dynbatch.cut", "dynbatch.prepare", "dynbatch.execute",
                 "dynbatch.finish"):
        waves = {s["args"].get("wave") for s in traced["spans"]
                 if s["name"] == name and "wave" in s["args"]}
        assert waves == {seq}, name
    cut = [s for s in traced["spans"] if s["name"] == "dynbatch.cut"
           and "wave" in s["args"]]
    assert cut[0]["args"]["batch"] == len(ROOTS)
    reqs = sorted(s["args"]["req"] for s in traced["spans"]
                  if s["name"] == "dynbatch.submit")
    assert reqs == sorted(f.req for f in futures) == list(range(len(ROOTS)))


def _check_levels(st):
    recs = st["levels"]
    assert len(recs) == st["iterations"]
    assert sum(r["mode"] == "push" for r in recs) == st["push_iters"]
    assert sum(r["mode"] == "pull" for r in recs) == st["pull_iters"]
    assert sum(r["retries"] for r in recs) == st["overflow_retries"]
    assert sum(r["total"] for r in recs) == st["edges_inspected"]
    for r in recs:
        push = r["mode"] == "push"
        assert set(r) == {"mode", "budget", "need", "total", "retries"} | (
            {"owner"} if push else set())   # only push expands edges
        assert all(isinstance(r[k], int)
                   for k in ("budget", "need", "total", "retries"))
        if push:
            assert r["need"] <= r["budget"] and r["total"] <= r["budget"]
            assert r["owner"] in ("merge", "search")
        else:
            assert r["budget"] == 0        # the dense pull has no budget


@pytest.mark.parametrize("case", ["bfs", "bfs_overflow", "cc"])
def test_level_records_agree_with_the_wave(graph, monkeypatch, case):
    csr, g = graph
    eng = (ConnectedComponentsRunner(g) if case == "cc"
           else MultiSourceBFSRunner(g))
    if case == "bfs_overflow":
        overflow_once(monkeypatch)
    eng.run_batch(ROOTS)
    st = eng.last_stats
    _check_levels(st)
    assert st["overflow_retries"] == (case == "bfs_overflow")
    modes = {r["mode"] for r in st["levels"]}
    assert "push" in modes


FLOOR = 1 << 8
RUNNERS = {"bfs": MultiSourceBFSRunner, "cc": ConnectedComponentsRunner,
           "sssp": SSSPRunner}


@pytest.fixture(scope="module")
def kron():
    """Scale-12 Kronecker graph (symmetrized, so CC runs on it too) and 32
    roots of nonzero degree."""
    n = 1 << 12
    src, dst = rmat_edges(12, 16, seed=3)
    csr = symmetrize_csr(csr_from_edges(src, dst, n))
    deg = np.diff(csr.indptr)
    roots = np.random.default_rng(0).choice(np.flatnonzero(deg > 0), 32,
                                            replace=False)
    return csr, build_local_graph(csr, csr), roots


def _rung(rec, floor):
    return max(floor, 1 << max(0, rec["need"] - 1).bit_length())


def _push_levels(st):
    return [r for r in st["levels"] if r["mode"] == "push"]


@pytest.mark.parametrize("algo", sorted(RUNNERS))
def test_each_push_level_picks_its_own_rung(kron, algo):
    csr, g, roots = kron
    eng = RUNNERS[algo](g, init_budget=FLOOR)
    res = eng.run(roots)
    st = eng.last_stats
    _check_levels(st)
    modes = [r["mode"] for r in st["levels"]]
    # push (wide) -> pull ... -> push (narrow)
    assert modes[0] == "push" and modes[-1] == "push" and "pull" in modes
    push = _push_levels(st)
    for r in push:
        assert r["retries"] == 0 and r["budget"] == _rung(r, FLOOR), r
    top = max(r["budget"] for r in push)
    assert push[-1]["budget"] < top        # the tail runs below the peak
    assert res.budget == st["budget"] == top
    np.testing.assert_array_equal(res.levels, msbfs_reference(g, roots))


@pytest.mark.parametrize("rung", [1 << 8, 1 << 12, 1 << 15, 1 << 20])
def test_override_is_the_floor_of_an_empty_push_level(kron, rung):
    """Roots without arcs: one push level with need 0, at exactly the
    override (capped at the edge count plus one)."""
    csr, g, _ = kron
    isolated = np.flatnonzero(np.diff(csr.indptr) == 0)
    assert isolated.size
    eng = MultiSourceBFSRunner(g)
    eng.run_batch(np.full(32, isolated[0]), budget=rung)
    floor = min(rung, int(csr.indices.size) + 1)
    assert eng.last_stats["levels"] == [dict(
        mode="push", budget=floor, need=0, total=0, retries=0,
        owner=owner_path(g.n_pad, floor))]
    assert eng.last_stats["budget"] == floor


def test_overflow_doubles_only_its_own_level(kron, monkeypatch):
    """The first push level re-runs at twice its rung; the later levels
    pick their rungs from their own need."""
    csr, g, roots = kron
    overflow_once(monkeypatch)
    eng = MultiSourceBFSRunner(g, init_budget=FLOOR)
    rows = eng.run_batch(roots)
    push = _push_levels(eng.last_stats)
    assert push[0]["retries"] == 1
    assert push[0]["budget"] == 2 * _rung(push[0], FLOOR)
    for r in push[1:]:
        assert r["retries"] == 0 and r["budget"] == _rung(r, FLOOR)
    assert eng.last_stats["budget"] == push[0]["budget"]
    np.testing.assert_array_equal(rows, msbfs_reference(g, roots))


@pytest.mark.parametrize("wave", ["stats", "plain"])
def test_traced_wave_records_agree(traced, wave):
    _check_levels(traced[wave])


def test_answers_the_same_with_the_profiler_on_and_off(traced):
    csr, g = traced["csr"], traced["g"]
    eng = MultiSourceBFSRunner(g)
    rows = eng.run_batch(ROOTS)
    np.testing.assert_array_equal(rows, traced["rows"])
    assert eng.last_stats["levels"] == traced["plain"]["levels"]
    for lv, r in zip(traced["served"], ROOTS):
        np.testing.assert_array_equal(np.asarray(lv, np.int64),
                                      bfs_oracle(csr, int(r)))


def test_gc_spans_install_once(graph):
    gc_spans()
    DynamicBatcher(MultiSourceBFSRunner(graph[1]), clock=lambda: 0.0)
    from repro.spans import _on_gc
    assert sum(cb is _on_gc for cb in gc.callbacks) == 1
