"""CPU tests of the Graph 500 kernel-2 cell (``graph500-kron-s22.batch32``).

    PYTHONPATH=src python -m pytest -q bench/tests

The configuration names a known program and output; ``trees.levels_from_
parents`` inverts the reference's parent trees; the cell's four readers
read what they should from a made-up run; and a run of the cell at scale
10 on the CPU is correct while the output's control fails the check.
"""
from __future__ import annotations

import json
import os
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import graph as bench_graph  # noqa: E402
import readings  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
from trees import levels_from_parents  # noqa: E402

CELL = "graph500-kron-s22.batch32"
READERS = ("parent_push_step_ms.batch", "parent_pull_step_ms.batch",
           "parent_pull_hits.batch", "parent_bw_share.batch")


def small_cfg(scale: int = 10) -> dict:
    _, cfg, _ = run.cell_inputs(run.load_manifest(), CELL)
    return dict(cfg, name=f"small-graph500-{scale}", scale=scale)


def test_configuration_names_a_known_program_and_output():
    man = run.load_manifest()
    cell, cfg, traffic = run.cell_inputs(man, CELL)
    program, output = run.served_program(cfg)
    assert program.name == "bfs_parents" and program.payload == "parents"
    assert Path(output.__file__).name == "parents.py"
    assert cell["chips"] == 1 and traffic["driver"] == "closed"
    kron = json.loads((BENCH / "configs" / "gap-kron-s22.json").read_text())
    # the same graph as gap-kron-s22, and only the scale cut from the spec
    for key in bench_graph.GENERATOR_KEYS:
        assert cfg[key] == kron[key]
    assert set(cfg["reduced"]) == {"scale"}
    assert cfg["published"]["scale"] == 26
    names = [m["name"] for m in run.cell_metrics(man, CELL, True)]
    assert names == list(READERS)


@pytest.mark.parametrize("generator", ["kron", "urand"])
def test_levels_from_parents_inverts_the_reference(generator):
    cfg = dict(small_cfg(10), generator=generator)
    indptr, indices = bench_graph.generate(cfg)
    roots = np.flatnonzero(np.diff(indptr))[::37][:32]
    parents = reference.bfs_parents(indptr, indices, roots)
    np.testing.assert_array_equal(levels_from_parents(parents),
                                  reference.bfs_levels(indptr, indices, roots))


def _made_up_run(indptr, indices, roots):
    """Two waves of 16 and 4 answered requests in 32-slot waves, with
    device times, per-level counters and a peak."""
    rows = reference.bfs_parents(indptr, indices, roots)
    waves = [types.SimpleNamespace(n_slots=32) for _ in range(2)]
    answered = [types.SimpleNamespace(
        row=row, future=types.SimpleNamespace(wave=waves[i >= 16]))
        for i, row in enumerate(rows)]
    levels = [dict(mode="push", hits=5), dict(mode="pull", hits=300),
              dict(mode="pull", hits=100)]
    return types.SimpleNamespace(
        answered=answered, deg=np.diff(indptr),
        waves=[{"levels": levels}, {"levels": levels[:2]}],
        peaks={"hbm_bytes_per_s": 1e9},
        trace={"busy_s": 0.5, "window_s": 1.0,
               "program_s": {"vp_push_step_parents": 0.25,
                             "vp_pull_step_parents": 0.75,
                             "vp_push_step": 9.0}}), rows


def test_readers_read_a_made_up_run():
    indptr, indices = bench_graph.generate(small_cfg(9))
    roots = np.flatnonzero(np.diff(indptr))[:20]
    data, rows = _made_up_run(indptr, indices, roots)
    got = {name: run.load_reader(name)(data) for name in READERS}
    assert got["parent_push_step_ms.batch"] == pytest.approx(125.0)
    assert got["parent_pull_step_ms.batch"] == pytest.approx(375.0)
    assert got["parent_pull_hits.batch"] == pytest.approx(350.0)
    levels = reference.bfs_levels(indptr, indices, roots)
    need = (readings.least_bytes(data, list(levels[:16]), 32)
            + readings.least_bytes(data, list(levels[16:]), 32)
            + 4 * int(np.count_nonzero(rows >= 0)))
    assert got["parent_bw_share.batch"] == pytest.approx(
        100.0 * need / (0.5 * 1e9))
    # a run of a program without parent steps or counters reads nothing
    data.trace["program_s"] = {"vp_push_step": 1.0}
    for w in data.waves:
        for lv in w["levels"]:
            lv.pop("hits", None)
    assert [run.load_reader(n)(data) for n in READERS[:3]] == [None] * 3
    data.trace = None
    assert run.load_reader(READERS[3])(data) is None


def test_small_run_is_correct_and_the_control_is_not():
    _, _, traffic = run.cell_inputs(run.load_manifest(), CELL)
    keep, lines = {}, []
    out = run.run_cell(
        {"chips": 1}, small_cfg(10), traffic,
        run.load_manifest()["end_to_end"], 2**33 + 7, 1.0, False,
        require_tpu=False, keep=keep, log=lines.append,
        cache_dir=Path(os.environ.get("TMPDIR", "/tmp")) / "bench-test-cache")
    assert out["correct"], out["checks"]
    assert [k for k in out["checks"]] == [
        "parent_not_an_edge", "parent_level_wrong", "tree_span_wrong",
        "unanswered", "rows_checked_min"]
    assert out["checks"]["rows_checked_min"]["value"] == 32
    assert "program: bfs_parents, output: parents" in lines
    assert "compilations_in_window: 0" in lines
    graph = (keep["indptr"], keep["indices"])
    np.testing.assert_array_equal(
        np.stack(keep["rows"]), reference.bfs_parents(*graph, keep["roots"]))
    output = run.load_output("parents")
    ctl = output.check(*graph, keep["roots"],
                       output.control(*graph, keep["roots"]))
    assert sum(c["value"] for c in ctl.values()) > 0
