"""CPU tests of the span reduction (``bench/spans.py``) and of the reader
of ``push_budget_fill.batch``.

    PYTHONPATH=src python -m pytest -q bench/tests

They check each span quantity on hand-made spans and modules, the reader
on hand-made waves, the collection of a CPU trace written by the
program's own spans, and the reduction of a trace recorded on a TPU v5e
against values recounted from it by a separate script.
"""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import run  # noqa: E402
import spans  # noqa: E402

DATA = BENCH / "tests" / "data"
SPANS_FILE = DATA / "small_spans.xplane.pb"
MS = 1e6


def synthetic():
    """One wave of two levels in a 100 ms window (ns), and what each
    quantity reads there."""
    window = (0.0, 100 * MS)
    modules = [("vp_init_state", 10 * MS, 11 * MS),
               ("vp_push_step", 14 * MS, 20 * MS),
               ("vp_pull_step", 25 * MS, 50 * MS),
               ("_plane_traversed", 51 * MS, 52 * MS)]
    t = 0
    sp = [
        ("dynbatch.cut", t, 1 * MS, 8 * MS, {"wave": 0, "batch": 32,
                                             "preempted": False}),
        ("dynbatch.prepare", t, 8 * MS, 9 * MS, {"wave": 0}),
        ("dynbatch.execute", t, 9 * MS, 60 * MS, {"wave": 0}),
        ("vp.wave", t, 9 * MS, 59 * MS, {"slots": 32, "budget": 64}),
        ("vp.init", t, 9 * MS, 9.5 * MS, {}),
        ("vp.sync", t, 9.5 * MS, 11.5 * MS, {"level": 0, "retry": 0}),
        ("vp.level.host", t, 11.5 * MS, 13.5 * MS,
         {"level": 0, "retry": 0, "mode": "push", "budget": 64}),
        ("vp.sync", t, 13.5 * MS, 21 * MS, {"level": 0, "retry": 0}),
        ("vp.level.host", t, 21 * MS, 24 * MS,
         {"level": 1, "retry": 0, "mode": "pull", "budget": 0}),
        ("vp.sync", t, 24 * MS, 50.5 * MS, {"level": 1, "retry": 0}),
        ("vp.rows", t, 50.5 * MS, 59 * MS, {"slots": 32}),
        ("dynbatch.finish", t, 60 * MS, 66 * MS, {"wave": 0}),
        ("host.gc", 1, 70 * MS, 71 * MS, {"generation": 2, "collected": 9}),
        ("dynbatch.cut", t, 66 * MS, 100 * MS, {}),
    ]
    return sp, modules, window


def test_span_quantities_by_hand():
    sp, modules, window = synthetic()
    assert spans.level_host_ms(sp) == pytest.approx((2 + 3) / 2)
    # syncs end 0.5, 1 and 0.5 ms after the init, push and pull modules
    assert spans.sync_lags(sp, modules) == pytest.approx(
        [0.0005, 0.001, 0.0005])
    assert spans.level_sync_lag_ms(sp, modules) == pytest.approx(2 / 3)
    assert spans.row_fetch_ms(sp) == pytest.approx(8.5)
    assert spans.wave_finish_ms(sp) == pytest.approx(6.0)
    # the closing cut carries no wave: not a refill
    assert spans.wave_refill_ms(sp) == pytest.approx(7.0)
    assert spans.wave_count(sp) == 1
    assert spans.wave_steps(sp) == [["push:64", "pull:0"]]


def test_sync_lag_pairs_in_order_under_clock_skew():
    sp, modules, window = synthetic()
    # the device timeline 1.5 ms early: the pull step now seems to start
    # before the push level's fetch returns, and the init before vp.init
    early = [(n, s - 1.5 * MS, e - 1.5 * MS) for n, s, e in modules]
    assert spans.sync_lags(sp, early) == pytest.approx(
        [0.002, 0.0025, 0.002])
    assert spans.clock_skew_min_ms(sp, early) == pytest.approx(0.5)
    assert spans.clock_skew_min_ms(sp, modules) == 0.0
    # a fetch with no module of its own pairs nothing
    assert spans.sync_lags(sp, early[1:]) == []


def test_idle_split_by_hand():
    sp, modules, window = synthetic()
    # idle: [0,10) [11,14) [20,25) [50,51) [52,100) = 10+3+5+1+48 ms
    assert spans.idle_s(modules, window) == pytest.approx(0.067)
    split = spans.idle_split(sp, modules, window)
    assert split["window -> vp_init_state"]["idle"] == pytest.approx(0.010)
    # [0,1) is under no span; the rest under cut, prepare, init, sync
    assert split["window -> vp_init_state"]["unattributed"] == \
        pytest.approx(0.001)
    assert split["window -> vp_init_state"]["dynbatch.cut"] == \
        pytest.approx(0.007)
    row = split["vp_push_step -> vp_pull_step"]
    assert row["vp.sync"] == pytest.approx(0.002)
    assert row["vp.level.host"] == pytest.approx(0.003)
    assert row["unattributed"] == pytest.approx(0.0)
    # [59,60) lies only under the containers vp.wave and dynbatch.execute
    tail = split["_plane_traversed -> window"]
    assert tail["unattributed"] == pytest.approx(0.001)
    assert spans.unattributed_s(sp, modules, window) == pytest.approx(0.002)
    assert spans.idle_unattributed_ms(sp, modules, window) == \
        pytest.approx(2.0)


def test_stalls_name_the_spans_open_in_them():
    sp, modules, window = synthetic()
    got = spans.stalls(sp, modules, window, least_s=0.02)
    assert [g["gap"] for g in got] == ["_plane_traversed -> window"]
    assert got[0]["seconds"] == pytest.approx(0.048)
    assert got[0]["spans"]["dynbatch.cut"] == pytest.approx(0.034)
    assert got[0]["spans"]["vp.rows"] == pytest.approx(0.007)
    assert got[0]["spans"]["host.gc"] == pytest.approx(0.001)


def test_report_holds_every_quantity():
    sp, modules, window = synthetic()
    rep = spans.report(sp, modules, window)
    for key in ("level_host_ms", "level_sync_lag_ms", "row_fetch_ms",
                "wave_finish_ms", "wave_refill_ms", "idle_unattributed_ms"):
        assert rep[key] is not None, key
    assert rep["span_counts"]["vp.sync"] == 3
    json.dumps(rep)


def test_no_spans_read_nothing():
    _, modules, window = synthetic()
    assert spans.level_host_ms([]) is None
    assert spans.level_sync_lag_ms([], modules) is None
    assert spans.wave_refill_ms([]) is None
    assert spans.idle_unattributed_ms([], modules, window) is None


# -- the reader of push_budget_fill.batch ------------------------------------

def _levels(*recs):
    return [dict(mode=m, budget=b, need=n, total=t, retries=0)
            for m, b, n, t in recs]


@pytest.mark.parametrize("waves,want", [
    # push levels fill 10 of 64 and 64 of 64; the pull level is not counted
    ([{"levels": _levels(("push", 64, 10, 10), ("pull", 0, 90, 900),
                         ("push", 64, 64, 64))}], 100.0 * 74 / 128),
    # two waves, one of them all pull
    ([{"levels": _levels(("push", 1024, 256, 256))},
      {"levels": _levels(("pull", 0, 5, 50))}], 25.0),
    # a program without the per-level counters reads nothing
    ([{"iterations": 3, "budget": 64}], None),
    ([{"levels": _levels(("pull", 0, 5, 50))}], None),
])
def test_push_budget_fill_reader(waves, want):
    got = run.load_reader("push_budget_fill.batch")(
        types.SimpleNamespace(waves=waves))
    assert got == (None if want is None else pytest.approx(want))


def test_push_budget_fill_on_a_wave_of_the_engine():
    from repro.core import MultiSourceBFSRunner, build_local_graph
    from repro.graph import csr_from_edges, rmat_edges, transpose_csr
    src, dst = rmat_edges(8, 8, seed=5)
    csr = csr_from_edges(src, dst, 256)
    eng = MultiSourceBFSRunner(build_local_graph(csr, transpose_csr(csr)))
    eng.run_batch(np.arange(32))
    wave = dict(eng.last_stats)
    got = run.load_reader("push_budget_fill.batch")(
        types.SimpleNamespace(waves=[wave]))
    push = [lv for lv in wave["levels"] if lv["mode"] == "push"]
    assert len(push) == wave["push_iters"] > 0
    assert 0 < got <= 100.0


# -- traces ----------------------------------------------------------------

def test_collect_a_cpu_trace_of_the_program(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core import MultiSourceBFSRunner, build_local_graph
    from repro.graph import csr_from_edges, rmat_edges, transpose_csr
    from repro.launch.dynbatch import DynamicBatcher
    src, dst = rmat_edges(7, 4, seed=2)
    csr = csr_from_edges(src, dst, 128)
    eng = MultiSourceBFSRunner(build_local_graph(csr, transpose_csr(csr)))
    eng.run_batch(np.arange(32))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run_batch(np.arange(32))            # before the window
        with TraceAnnotation(spans.WINDOW_EVENT):
            with DynamicBatcher(eng, window=0.01, max_batch=32) as b:
                futures = [b.submit(r) for r in (1, 2, 3)]
                for f in futures:
                    f.result(timeout=60)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    sp, window = spans.read_file(path)
    names = {x[0] for x in sp}
    assert {"vp.wave", "vp.sync", "vp.level.host", "vp.rows",
            "dynbatch.submit", "dynbatch.execute"} <= names
    assert spans.wave_count(sp) == 1           # the window's wave only
    assert all(window[0] <= s <= e <= window[1] for _, _, s, e, _ in sp)
    assert sorted(a["req"] for n, _, _, _, a in sp
                  if n == "dynbatch.submit") == [0, 1, 2]


@pytest.mark.skipif(not SPANS_FILE.exists(), reason="no recorded trace")
def test_reduce_recorded_tpu_trace_with_spans():
    from trace import read_xplane, reduce
    expect = json.loads((DATA / "small_spans.json").read_text())
    summary = reduce(read_xplane(SPANS_FILE))
    sp, window = spans.read_file(SPANS_FILE)
    rep = spans.report(sp, summary["modules"], window)
    assert rep["span_counts"] == expect["span_counts"]
    for key, want in expect["values"].items():
        assert rep[key] == pytest.approx(want, rel=1e-9), key
