#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``workloads`` in ``BENCHMARK.json``) pairs a configuration
(``bench/configs/<config>.json``) with a traffic mix
(``bench/traffic/<traffic>.json``, whose ``driver`` names a module in
``bench/drivers/``).  Each metric is a reader in ``bench/metrics/<name>.py``.
A configuration names its graph, its serving settings, the vertex program
that serves it (``program``: a name ``repro.core.get_program`` knows, given
to ``build_engine`` as ``algo``) and what its answers are (``output``: a
module ``bench/outputs/<output>.py``, which says which vertices a row
reaches, checks sampled rows against the plain reference and gives the
control that must fail that check).  Adding a configuration, mix, output
or metric is adding files and entries; this file needs no edit.

The run: check that JAX sees a TPU with enough chips; load the graph from
``bench/cache/`` (generating it on the first run); warm every program shape
the served waves can use; build the configuration's engine with
``repro.launch.serve.build_engine`` and serve it through
``repro.launch.dynbatch.DynamicBatcher``; let the mix's driver submit
requests for ``--seconds`` and drain them; then check a sample of the
answered rows, drawn from the seed, with the configuration's output module.
``--trace 1`` profiles the window and reports the per-layer metrics instead
of the end-to-end ones.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared with its limit).  The checks are also
the last lines of stderr.  No TPU, too few chips, or any failure before the
result (a configuration naming no known program or output among them): a
non-zero exit and no result line.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import drivers  # noqa: E402
import graph as bench_graph  # noqa: E402
from readings import latencies  # noqa: E402
from roots import make_sampler  # noqa: E402

CHECK_SAMPLE = 32          # answered requests compared with the reference
DRAIN_SECONDS = 150.0      # longest wait for answers after the window
LADDER_TOP_SHARE = 8       # warm push budgets up to E / this (see warm_up)
MAX_WAVE_LINES = 40        # waves described on stdout


class RunFailed(RuntimeError):
    """The run cannot produce a result line."""


# -- the manifest ------------------------------------------------------------

def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_inputs(manifest: dict, workload: str, root: Path = ROOT):
    """(cell, configuration, traffic mix) of one workload name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise RunFailed(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, traffic


def cell_metrics(manifest: dict, workload: str, trace: bool) -> list[dict]:
    group = manifest["per_layer"] if trace else manifest["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def _load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if not path.exists():
        raise RunFailed(f"no {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    return _load_module("metrics", name).read


def load_output(name: str):
    """The module ``bench/outputs/<name>.py``: ``reached``, ``check``,
    ``control``."""
    if not isinstance(name, str) or not name.replace("_", "").isalnum():
        raise RunFailed(f"bad output name {name!r}")
    return _load_module("outputs", name)


def served_program(cfg: dict):
    """(vertex program, output module) the configuration names."""
    from repro.core import get_program
    for key in ("program", "output"):
        if key not in cfg:
            raise RunFailed(f"configuration {cfg.get('name')!r} names no "
                            f"{key!r}")
    try:
        program = get_program(cfg["program"])
    except (TypeError, ValueError) as e:
        raise RunFailed(str(e)) from None
    return program, load_output(cfg["output"])


# -- the device ---------------------------------------------------------------

def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(info: dict, chips: int) -> None:
    if info["platform"] != "tpu":
        raise RunFailed(f"no TPU: JAX runs on {info['platform']!r}")
    if info["count"] < chips:
        raise RunFailed(f"the cell needs {chips} chips, JAX sees "
                        f"{info['count']}")


def device_peaks(kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise RunFailed(f"device {kind!r} is not in bench/peaks.json")
    return peaks[kind]


class CompileCounter:
    """Counts XLA compilations (including loads from the persistent cache)
    from the moment ``start`` is called."""

    def __init__(self, jax):
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.monotonic())

    def count_between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


# -- the system under test ----------------------------------------------------

class Recorder:
    """The engine as the batcher sees it, keeping each wave's counters."""

    def __init__(self, engine):
        self.engine = engine
        self.waves: list[dict] = []

    @property
    def num_vertices(self):
        return self.engine.num_vertices

    @property
    def out_deg(self):
        return self.engine.out_deg

    @property
    def last_stats(self):
        return self.engine.last_stats

    def run_batch(self, roots, **kw):
        t0 = time.monotonic()
        rows = self.engine.run_batch(roots, **kw)
        st = {k: v for k, v in self.engine.last_stats.items()
              if k != "traversed_per_plane"}
        st.update(t_start=t0, t_end=time.monotonic())
        self.waves.append(st)
        return rows


def build(indptr, indices, program):
    """The program's local engine over a CSR (symmetric: CSC is CSR)."""
    from repro.graph.csr import CSRGraph
    from repro.launch.serve import build_engine
    csr = CSRGraph(len(indptr) - 1, indptr, indices)
    engine, _ = build_engine(types.SimpleNamespace(csr=csr, csc=csr),
                             algo=program.name, distributed=False)
    return engine


def stand_in(n: int, num_arcs: int, undirected: bool):
    """A CSR of ``n`` vertices and ``num_arcs`` arcs in which vertex 0 has
    no arcs and a wave from vertex 1 reaches a dense pull level.

    Directed: vertex 1 holds every arc, all to vertex 2.  Undirected (the
    engine symmetrizes the graph and collapses repeated arcs, so the
    stand-in must be a simple symmetric graph already): vertex 1 is joined
    to every vertex from 2 on, and the other edges join vertices i and
    i + j (mod n - 2) among them, j = 1, 2, ... as far as needed.  From
    vertex 1 a push level reaches everything and the next level pulls."""
    if not undirected:
        indptr = np.zeros(n + 1, np.int64)
        indptr[2:] = num_arcs
        return indptr, np.full(num_arcs, 2, np.int32)
    m = n - 2
    extra = num_arcs // 2 - m
    if num_arcs % 2 or not 0 <= extra <= m * ((m - 1) // 2):
        raise RunFailed(f"no simple symmetric stand-in with {n} vertices "
                        f"and {num_arcs} arcs")
    step, i = np.divmod(np.arange(extra, dtype=np.int64), m)
    src = np.concatenate([np.ones(m, np.int64), 2 + i])
    dst = np.concatenate([np.arange(2, n, dtype=np.int64),
                          2 + (i + step + 1) % m])
    return bench_graph.csr_from_edges(src, dst, n)


def warm_up(n: int, num_arcs: int, slots: int, program) -> dict:
    """Compile, outside the window, every program a served wave can run.

    Program shapes depend only on the vertex program, |V|, |E|, the plane
    slots and the push edge budget, so a stand-in graph of the same sizes
    (``stand_in``) warms them through the engine's public entry
    (``run_batch(roots, budget=)``) in a few short waves: vertex 0 has no
    arcs (a push level at the given budget finds nothing), and a wave
    from vertex 1 reaches a dense pull level.  The push budget doubles from
    the engine's ``init_budget`` while a level needs more; a push level
    never needs more than ``m_u / alpha`` arcs when it follows a push
    level, so warming up to ``E / LADDER_TOP_SHARE`` covers the ladder
    with room.  The run counts compilations in the window to show it.
    """
    t0 = time.monotonic()
    engine = build(*stand_in(n, num_arcs, program.undirected), program)
    budget = min(int(engine.init_budget), num_arcs + 1)
    rungs = []
    while True:
        rungs.append(budget)
        engine.run_batch(np.zeros(slots, np.int64), budget=budget)
        if budget * LADDER_TOP_SHARE >= num_arcs or budget * 2 > num_arcs + 1:
            break
        budget *= 2
    engine.run_batch(np.ones(slots, np.int64))
    del engine
    gc.collect()
    return {"warm_push_budgets": rungs,
            "warm_seconds": time.monotonic() - t0}


# -- one run ------------------------------------------------------------------

def traversed_edges(deg: np.ndarray, reached) -> int:
    return int(deg[reached].sum())


def run_cell(cell: dict, cfg: dict, traffic: dict, metrics: list[dict],
             seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, wrap_engine=None,
             cache_dir: Path = bench_graph.CACHE_DIR,
             t_process: float = T_PROCESS, log=print, keep: dict | None = None):
    """One run of one cell; returns the result object for the last line.

    ``keep``, where given, receives the checked sample (``roots``,
    ``rows``), the graph (``indptr``, ``indices``) and each request's
    latency (``latencies``)."""
    import jax
    from repro.launch.cache import REPO_CACHE_DIR
    from repro.launch.dynbatch import DynamicBatcher

    program, output = served_program(cfg)
    info = device_info(jax)
    if require_tpu:
        require_chips(info, int(cell["chips"]))
    peaks = device_peaks(info["kind"]) if require_tpu else None
    log(f"device: {info['kind']} x{info['count']}")
    # the repo's cache directory inside this checkout, whatever the
    # environment names: runs of one checkout share it, two checkouts never
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile_cache: {REPO_CACHE_DIR}")
    compiles = CompileCounter(jax)

    indptr, indices, ginfo = bench_graph.load_graph(cfg, cache_dir)
    n, num_arcs = len(indptr) - 1, int(indices.size)
    deg = np.diff(indptr)
    log(f"graph: {cfg['name']} {ginfo['graph_source']} in "
        f"{ginfo['graph_seconds']} s, {n} vertices, {num_arcs} arcs")
    log(f"program: {program.name}, output: {cfg['output']}")

    serving = cfg["serving"]
    slots = -(-int(serving["max_batch"]) // 32) * 32
    warm = warm_up(n, num_arcs, slots, program)
    log(f"warm_push_budgets: {warm['warm_push_budgets']}")
    log(f"warm_seconds: {warm['warm_seconds']}")
    t0 = time.monotonic()
    engine = build(indptr, indices, program)
    log(f"engine_build_seconds: {time.monotonic() - t0}")
    if wrap_engine is not None:
        engine = wrap_engine(engine)
    rec = Recorder(engine)
    batcher = DynamicBatcher(rec, out_deg=deg,
                             window=float(serving["window_s"]),
                             max_batch=int(serving["max_batch"]))
    driver = drivers.load(traffic["driver"])
    next_root = make_sampler(traffic["roots"], deg, seed)

    profiler = None
    trace_dir = cache_dir / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        profiler = jax.profiler
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        profiler.start_trace(str(trace_dir), profiler_options=opts)

    t_window = time.monotonic()
    setup_s = t_window - t_process
    deadline = t_window + seconds + DRAIN_SECONDS
    try:
        if profiler is not None:
            with profiler.TraceAnnotation("bench.window"):
                requests = driver.drive(batcher, traffic, next_root,
                                        t_window, seconds, deadline)
                batcher.close(drain=True,
                              timeout=max(deadline - time.monotonic(), 1))
        else:
            requests = driver.drive(batcher, traffic, next_root, t_window,
                                    seconds, deadline)
            batcher.close(drain=True,
                          timeout=max(deadline - time.monotonic(), 1))
    finally:
        if profiler is not None:
            profiler.stop_trace()
    t_close = time.monotonic()
    n_compiles = compiles.count_between(t_window, t_close)
    mem = (jax.devices()[0].memory_stats() or {})
    peak = mem.get("peak_bytes_in_use")

    ok = [r for r in requests
          if r.future.done() and r.future.exception() is None]
    answers = [r.t_answer for r in ok]
    for r in ok:
        r.row = r.future.result(timeout=0)
        r.traversed = traversed_edges(deg, output.reached(r.row))
    late = [r.future.t_submit - r.t_due for r in requests]
    log(f"compilations_in_window: {n_compiles}")
    log(f"memory_peak_bytes: {peak}")
    log(f"requests: {len(requests)} sent, {len(ok)} answered")
    log(f"generator_late_s: max {max(late, default=0.0)} "
        f"median {float(np.median(late)) if late else 0.0}")
    for i, w in enumerate(rec.waves[:MAX_WAVE_LINES]):
        log(f"wave {i}: batch {w.get('batch')} levels {w.get('iterations')}"
            f" push {w.get('push_iters')} pull {w.get('pull_iters')}"
            f" overflow_retries {w.get('overflow_retries')}"
            f" budget {w.get('budget')} seconds {w['t_end'] - w['t_start']}")

    summary = None
    if trace:
        import trace as bench_trace
        summary = bench_trace.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)

    data = types.SimpleNamespace(
        setup_s=setup_s, t_window=t_window, t_close=t_close,
        t_last=max(answers, default=None), requests=requests, answered=ok,
        waves=rec.waves, deg=deg, trace=summary, peaks=peaks)
    values = {}
    for m in metrics:
        v = load_reader(m["name"])(data)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    lat = latencies(data) if keep is not None else None
    # free the program's state before the reference runs
    del batcher, rec, engine, data
    gc.collect()

    rng = np.random.default_rng([seed, 1])
    pick = (rng.choice(len(ok), size=min(CHECK_SAMPLE, len(ok)),
                       replace=False) if ok else np.zeros(0, int))
    roots = [ok[i].root for i in pick]
    rows = [ok[i].row for i in pick]
    t0 = time.monotonic()
    answer_checks = output.check(indptr, indices, roots, rows)
    log(f"reference_seconds: {time.monotonic() - t0}")
    if keep is not None:
        keep.update(latencies=[(r.t_due - t_window, x) for r, x in
                               zip(requests, lat)],
                    roots=roots, rows=rows, indptr=indptr, indices=indices)
    failed = len(requests) - len(ok)
    checks = dict(answer_checks)
    checks["unanswered"] = {"value": failed, "limit": 0}
    checks["rows_checked_min"] = {"value": int(pick.size), "limit": 1}
    correct = (all(c["value"] <= c["limit"] for c in answer_checks.values())
               and failed <= 0 and pick.size >= 1)
    device = dict(info, memory_peak_bytes=peak)
    if summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    out = {"correct": bool(correct), "attempted": len(requests),
           "failed": failed, "metrics": values, "device": device}
    if summary is not None:
        out["breakdown"] = summary["breakdown"]
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        manifest = load_manifest()
        cell, cfg, traffic = cell_inputs(manifest, args.workload)
        metrics = cell_metrics(manifest, args.workload, bool(args.trace))
        out = run_cell(cell, cfg, traffic, metrics, args.seed, args.seconds,
                       bool(args.trace))
    except RunFailed as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 3
    sys.stdout.flush()
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
