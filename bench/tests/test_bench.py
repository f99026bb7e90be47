"""CPU tests of the chip benchmark (``bench/``).

    PYTHONPATH=src python -m pytest -q bench/tests

They check the manifest and the files it names, the copied generators
against the program's, the reference against the program's numpy oracle,
the trace reduction on a trace recorded on a TPU v5e, the byte count of
``traversal_bw_share.batch`` by hand, that ``run.py`` refuses to run off a
TPU, and that the comparison deciding ``correct`` fails for the control
and for each fault a single-chip cell can have.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
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

TRACE_FILE = BENCH / "tests" / "data" / "small_window.xplane.pb"


def tiny_cfg(scale: int = 8, generator: str = "kron") -> dict:
    cfg = json.loads((BENCH / "configs" / "gap-kron-s22.json").read_text())
    cfg.update(name=f"tiny-{generator}", scale=scale, generator=generator)
    return cfg


# -- the manifest --------------------------------------------------------------

def test_manifest_names_resolve():
    man = run.load_manifest()
    assert man["command"] == ["python3", "bench/run.py"]
    assert man["paths"] == ["bench"]
    e2e = {m["name"] for m in man["end_to_end"]}
    assert {"teps", "latency_p50_s", "latency_p90_s", "setup_s"} <= e2e
    cells = {w["name"] for w in man["workloads"]}
    for cfg in man["configs"]:
        data = json.loads((ROOT / cfg["file"]).read_text())
        assert data["name"] == cfg["name"]
        assert data["source"] == cfg["source"]
        for key in cfg["reduced"]:
            assert data[key] != data["published"][key]
    for w in man["workloads"]:
        cell, cfg, traffic = run.cell_inputs(man, w["name"])
        assert cfg["name"] == w["config"]
        import drivers
        assert callable(drivers.load(traffic["driver"]).drive)
        for trace in (False, True):
            names = [m["name"] for m in run.cell_metrics(man, w["name"],
                                                         trace)]
            assert names, (w["name"], trace)
            for name in names:
                assert callable(run.load_reader(name))
    for m in man["per_layer"]:
        assert set(m["workloads"]) <= cells
        assert m["moves"] in e2e


@pytest.mark.parametrize("path", sorted((BENCH / "traffic").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_mix_names_a_driver_and_a_sampler(path):
    import drivers
    import roots
    traffic = json.loads(path.read_text())
    assert callable(drivers.load(traffic["driver"]).drive)
    deg = np.array([0, 1, 2, 1])
    draw = roots.make_sampler(traffic["roots"], deg, 3000000001)
    assert all(deg[draw()] > 0 for _ in range(64))


# -- the graph -------------------------------------------------------------------

def test_copied_generators_match_the_program():
    from repro.graph import generators
    for got, want in [
            (bench_graph.rmat_edges(10, 4, 7),
             generators.rmat_edges(10, 4, seed=7)),
            (bench_graph.uniform_edges(1024, 4096, 7),
             generators.uniform_edges(1024, 4096, seed=7))]:
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])


def test_csr_matches_the_programs_dataset():
    from repro.graph import get_dataset
    ds = get_dataset("small-12-8", seed=1, cache=False)
    cfg = tiny_cfg(12)
    cfg.update(edge_factor=8, graph_seed=1)
    indptr, indices = bench_graph.generate(cfg)
    np.testing.assert_array_equal(indptr, ds.csr.indptr)
    np.testing.assert_array_equal(indices, ds.csr.indices)


def test_graph_cache_round_trip(tmp_path):
    cfg = tiny_cfg(6)
    a = bench_graph.load_graph(cfg, tmp_path)
    b = bench_graph.load_graph(cfg, tmp_path)
    assert a[2]["graph_source"] == "generated"
    assert b[2]["graph_source"] == "cache"
    np.testing.assert_array_equal(a[1], b[1])


# -- the reference -----------------------------------------------------------------

@pytest.mark.parametrize("generator", ["kron", "urand"])
def test_reference_agrees_with_the_oracle(generator):
    from repro.core import bfs_oracle
    from repro.graph.csr import CSRGraph
    indptr, indices = bench_graph.generate(tiny_cfg(9, generator))
    csr = CSRGraph(len(indptr) - 1, indptr, indices)
    rng = np.random.default_rng(0)
    for k in (1, 5, 32, 40):
        roots = rng.integers(0, csr.num_vertices, k)
        roots[-1] = roots[0]                    # a duplicate root
        got = reference.bfs_levels(indptr, indices, roots)
        for r, row in zip(roots, got):
            np.testing.assert_array_equal(row, bfs_oracle(csr, int(r)))


def test_control_fails_the_comparison():
    indptr, indices = bench_graph.generate(tiny_cfg(10))
    roots = np.flatnonzero(np.diff(indptr))[:32]
    want = reference.bfs_levels(indptr, indices, roots)
    assert reference.mismatches(want, want) == 0
    ctl = reference.truncated_push_levels(indptr, indices, roots, budget=64)
    assert reference.mismatches(ctl, want) > 0


# -- the trace reduction -----------------------------------------------------------

def test_reduce_known_numbers_synthetic():
    from trace import reduce, step_gaps
    ms = 1e6
    raw = {"window": (0.0, 100 * ms), "devices": [{
        "modules": [("jit_vp_init_state(1)", 10 * ms, 11 * ms),
                    ("jit_vp_push_step(2)", 12 * ms, 20 * ms),
                    ("jit_vp_pull_step(3)", 23 * ms, 53 * ms),
                    ("jit__plane_traversed(4)", 54 * ms, 55 * ms)],
        "ops": [("a", 10 * ms, 11 * ms), ("b", 12 * ms, 20 * ms),
                ("c", 23 * ms, 40 * ms), ("d", 30 * ms, 53 * ms),
                ("e", 54 * ms, 55 * ms)]}]}
    s = reduce(raw)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.040)
    assert s["program_s"]["vp_pull_step"] == pytest.approx(0.030)
    assert step_gaps(s["modules"]) == pytest.approx([0.001, 0.003])
    assert s["breakdown"]["device_ops"][0] == ["vp_pull_step/d",
                                               pytest.approx(0.023)]
    assert s["breakdown"]["idle_gaps"][0] == [
        "vp_push_step -> vp_pull_step", pytest.approx(0.003)]


@pytest.mark.skipif(not TRACE_FILE.exists(), reason="no recorded trace")
def test_reduce_recorded_tpu_trace():
    from trace import read_xplane, reduce, step_gaps
    expect = json.loads((TRACE_FILE.parent / "small_window.json").read_text())
    s = reduce(read_xplane(TRACE_FILE))
    assert s["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert s["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    for name, sec in expect["program_s"].items():
        assert s["program_s"][name] == pytest.approx(sec, rel=1e-9)
    assert len(step_gaps(s["modules"])) == expect["step_gaps"]


# -- the byte count of traversal_bw_share.batch -----------------------------------

def test_least_bytes_by_hand():
    # path 0-1-2-3 plus an isolated vertex 4, symmetric: degrees 1,2,2,1,0
    indptr = np.array([0, 1, 3, 5, 6, 6])
    indices = np.array([1, 0, 2, 1, 3, 2])
    deg = np.diff(indptr)
    rows = reference.bfs_levels(indptr, indices, [0, 3])
    run_ = types.SimpleNamespace(deg=deg)
    # rows: root 0 -> 0,1,2,3,INF; root 3 -> 3,2,1,0,INF
    # level 0: frontier {0,3} m_f=2, unreached by some {0..4} m_u=6 -> 2
    # level 1: frontier {1,2} m_f=4, unreached by some {0..4} m_u=6 -> 4
    # level 2: frontier {1,2} m_f=4, unreached by some {0,3,4} m_u=2 -> 2
    # level 3: frontier {0,3} m_f=2, unreached by some {4} m_u=0    -> 0
    planes = 2 * 4 * 1 * 5
    assert readings.least_bytes(run_, list(rows), 32) == \
        4 * (2 + 4 + 2 + 0) + 4 * planes


# -- refusing to run off the chip ----------------------------------------------------

def _run_cli(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gap-kron-s22.batch32",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result_line(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_run_refuses_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert not _has_result_line(p.stdout)
    assert "no TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    env = {"PYTHONPATH": ""}
    p = _run_cli(tmp_path, env)
    assert p.returncode != 0
    assert not _has_result_line(p.stdout)


# -- the comparison that decides `correct` --------------------------------------------

class Faulty:
    """The engine with one fault planted where its answers are produced."""

    def __init__(self, engine, kind: str):
        self.engine, self.kind = engine, kind

    num_vertices = property(lambda self: self.engine.num_vertices)
    out_deg = property(lambda self: self.engine.out_deg)
    last_stats = property(lambda self: self.engine.last_stats)

    def run_batch(self, roots, **kw):
        roots = np.asarray(roots)
        if self.kind == "half_batch":
            # half the planes traversed; the rest repeat their answers
            h = max(len(roots) // 2, 1)
            part = np.array(self.engine.run_batch(roots[:h], **kw))
            return np.concatenate([part, part])[: len(roots)]
        rows = np.array(self.engine.run_batch(roots, **kw))
        if self.kind == "state_unchanged":
            # every step hands its input state back: only the roots reached
            rows[:] = reference.INF
            rows[np.arange(len(roots)), roots] = 0
        elif self.kind == "answer_altered":
            for row in rows:
                v = int(np.flatnonzero(row > 0)[0])
                row[v] += 1
        return rows


QUEUE_WAIT = {"name": "queue_wait_ms.served", "unit": "ms"}


def _tiny_run(wrap=None, traffic="closed32", seed=2**33 + 5):
    man = run.load_manifest()
    cfg = tiny_cfg(8)
    tr = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    if tr["driver"] == "poisson":
        tr["rate_per_s"] = 40.0
    metrics = man["end_to_end"] + [QUEUE_WAIT]
    return run.run_cell({"chips": 1}, cfg, tr, metrics, seed, 1.0,
                        False, require_tpu=False, wrap_engine=wrap,
                        cache_dir=Path(os.environ.get("TMPDIR", "/tmp"))
                        / "bench-test-cache", log=lambda *a: None)


@pytest.mark.parametrize("traffic", ["closed32", "poisson-zipf"])
def test_sound_run_is_correct(traffic):
    out = _tiny_run(traffic=traffic)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"teps", "latency_p50_s", "latency_p90_s",
                                   "setup_s", QUEUE_WAIT["name"]}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
def test_fault_is_not_correct(kind):
    out = _tiny_run(wrap=lambda e: Faulty(e, kind))
    assert not out["correct"]
    assert out["checks"]["wrong_levels"]["value"] > 0
