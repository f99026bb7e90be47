#!/usr/bin/env python3
"""Smoke run of the served BFS path on a TPU, through the user entry points.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded engine on a 4-chip mesh

One chip: generates the paper's Graph500-class ``rmat22-16`` from its seed
(4,194,304 vertices, edge factor 16, symmetrized), builds the engine with
``repro.launch.serve.build_engine(..., distributed=False)``, warms every
wave it will serve, then serves 64 single-root requests (uniform over
vertices with out-degree > 0) through ``DynamicBatcher(max_batch=32)``.
Checks: every request resolves with a level row, and the rows of
``CHECK_ROOTS`` roots equal the numpy oracle ``repro.core.bfs_oracle``.
Then one 32-root wave through the Pallas propagate kernels must equal the
jnp wave on the same device graph bit for bit.  That wave runs on
``rmat18-16``, the paper's next-smaller Table I graph: the kernels'
per-edge loop took 763 s for one wave at ``rmat22-16`` on a TPU v5e,
against 14 s for the jnp wave, which leaves no room in the run's time.

``--chips 4`` runs only the sharded path: ``build_engine(...,
distributed=True)`` over a 4-device mesh on the same graph, one 32-root
``run_batch``, the same oracle check, and a check that every graph array
spans the four devices with balanced per-device memory.

The engine runs bare (no ``EngineSupervisor``), so a kernel fault fails the
run instead of demoting to another path.  Any failure, or a backend other
than TPU, exits non-zero before the result line.  The last line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GRAPH = "rmat22-16"
PALLAS_GRAPH = "rmat18-16"
REQUESTS = 64
MAX_BATCH = 32
CHECK_ROOTS = 4
SEED = 0
DRAIN_TIMEOUT = 900.0          # seconds the batcher may take to serve all


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, missing or failed result."""


def report(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(info: dict, chips: int) -> None:
    if info["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: JAX runs on {info['platform']!r}")
    if info["count"] < chips:
        raise SmokeFailure(f"need {chips} chips, JAX sees {info['count']}")


def generate(graph: str):
    """The dataset from its seed, uncached; returns (dataset, seconds)."""
    from repro.graph import get_dataset
    t0 = time.perf_counter()
    ds = get_dataset(graph, cache=False)
    return ds, time.perf_counter() - t0


def sample_roots(deg, count: int, seed: int = SEED):
    """Uniform request roots over the vertices with out-degree > 0."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return rng.choice(np.flatnonzero(deg > 0), count, replace=False)


def device_bytes(arrays) -> int:
    """Bytes of distinct device buffers (shared arrays count once)."""
    return sum({id(a): a.nbytes for a in arrays}.values())


def check_rows(csr, roots, rows) -> int:
    """Compare level rows with the numpy oracle; returns roots checked."""
    import numpy as np
    from repro.core import bfs_oracle
    for root, row in zip(roots, rows):
        want = bfs_oracle(csr, int(root))
        got = np.asarray(row, np.int64)
        if not np.array_equal(got, want):
            bad = int(np.sum(got != want))
            raise SmokeFailure(f"root {int(root)}: {bad} levels differ "
                               "from the oracle")
    return len(roots)


def warm_waves(engine, roots, max_batch: int = MAX_BATCH) -> list[float]:
    """Run every wave the stream will cut once, so the served waves compile
    nothing; returns the seconds of each (compilation included)."""
    from repro.core import bitmap
    from repro.launch.dynbatch import plane_wave_sizes
    assert plane_wave_sizes(max_batch)[-1] == max_batch
    secs = []
    for start in range(0, len(roots), max_batch):
        wave, _ = bitmap.pad_plane_slots(roots[start:start + max_batch])
        t0 = time.perf_counter()
        engine.run_batch(wave)
        secs.append(time.perf_counter() - t0)
    return secs


def serve(engine, deg, roots, max_batch: int = MAX_BATCH,
          window: float = 0.05) -> tuple[dict, list]:
    """Serve one request per root through the dynamic batcher; every
    request must resolve with a level row.  Returns (stats, rows)."""
    from repro.launch.dynbatch import DynamicBatcher
    batcher = DynamicBatcher(engine, out_deg=deg, window=window,
                             max_batch=max_batch)
    futures = [batcher.submit(int(r)) for r in roots]
    batcher.close(drain=True, timeout=DRAIN_TIMEOUT)
    rows = []
    for f in futures:
        if not f.done():
            raise SmokeFailure(f"request for root {f.root} never resolved")
        exc = f.exception()
        if exc is not None:
            raise SmokeFailure(f"request for root {f.root} failed: {exc!r}")
        rows.append(f.result(timeout=0))
    stats = batcher.stats()
    stats["wave_seconds"] = [w.seconds for w in batcher.waves]
    stats["wave_sizes"] = [w.batch for w in batcher.waves]
    if stats["requests"] != len(roots):
        raise SmokeFailure(f"served {stats['requests']} of {len(roots)}")
    return stats, rows


def pallas_wave(g, roots, jnp_rows):
    """One wave through the Pallas propagate kernels on the same device
    graph; its rows must equal the jnp rows bit for bit."""
    import numpy as np
    from repro.core import MultiSourceBFSRunner
    runner = MultiSourceBFSRunner(g, use_pallas=True)
    t0 = time.perf_counter()
    rows = runner.run_batch(roots)
    secs = time.perf_counter() - t0
    if not np.array_equal(rows, jnp_rows):
        bad = int(np.sum(rows != jnp_rows))
        raise SmokeFailure(f"Pallas wave differs from jnp in {bad} levels")
    return secs, runner.last_stats


def one_chip(graph: str = GRAPH, pallas_graph: str = PALLAS_GRAPH,
             requests: int = REQUESTS, max_batch: int = MAX_BATCH) -> None:
    import jax
    import numpy as np
    from repro.core import count_traversed_edges
    from repro.launch.serve import build_engine

    ds, gen_s = generate(graph)
    report("graph", graph)
    report("setup_generate_seconds", gen_s)
    report("num_vertices", ds.csr.num_vertices)
    report("num_stored_edges", ds.csr.num_edges)
    t0 = time.perf_counter()
    engine, deg = build_engine(ds, distributed=False)
    report("setup_build_engine_seconds", time.perf_counter() - t0)
    g = engine.g
    report("local_graph_bytes", device_bytes(
        getattr(g, f.name) for f in dataclasses.fields(g)
        if f.name not in ("n", "n_pad")))
    roots = sample_roots(deg, requests)

    warm = warm_waves(engine, roots, max_batch)
    report("warmup_wave_seconds_incl_compile", warm)
    stats, rows = serve(engine, deg, roots, max_batch)
    report("requests_served", f"{stats['requests']} of {requests}")
    report("waves", stats["waves"])
    report("wave_sizes", stats["wave_sizes"])
    report("wave_seconds", stats["wave_seconds"])
    report("compile_seconds_estimate",
           sum(warm) - sum(stats["wave_seconds"]))
    dev = jax.devices()[0]
    report(f"aggregate_teps[{dev.platform} {dev.device_kind}]",
           stats.get("aggregate_teps"))
    report("latency_p50_seconds", stats.get("latency_p50"))

    checked = check_rows(ds.csr, roots[:CHECK_ROOTS], rows[:CHECK_ROOTS])
    report("oracle_roots_matched", f"{checked} of {checked}")

    if pallas_graph != graph:
        ds, _ = generate(pallas_graph)
        engine, deg = build_engine(ds, distributed=False)
        g = engine.g
    report("pallas_graph", pallas_graph)
    wave = sample_roots(deg, max_batch)
    jnp_rows = np.asarray(engine.run_batch(wave))
    p_secs, p_stats = pallas_wave(g, wave, jnp_rows)
    report("pallas_wave_bit_exact", True)
    report("pallas_wave_seconds_incl_compile", p_secs)
    report("pallas_wave_traversed_edges",
           count_traversed_edges(deg, jnp_rows))
    report("pallas_wave_levels", p_stats.get("iterations"))
    report("pallas_wave_edges_inspected", p_stats.get("edges_inspected"))
    mem = dev.memory_stats() or {}
    report("peak_bytes_in_use", mem.get("peak_bytes_in_use", "not reported"))


def sharded(n_chips: int, graph: str = GRAPH,
            max_batch: int = MAX_BATCH) -> None:
    import jax
    import numpy as np
    from repro.core import count_traversed_edges
    from repro.launch.serve import build_engine

    if jax.device_count() != n_chips:
        raise SmokeFailure(f"need exactly {n_chips} devices, JAX sees "
                           f"{jax.device_count()}")
    ds, gen_s = generate(graph)
    report("graph", graph)
    report("setup_generate_seconds", gen_s)
    t0 = time.perf_counter()
    engine, deg = build_engine(ds, distributed=True)
    report("setup_build_engine_seconds", time.perf_counter() - t0)
    arrays = {"out_indptr": engine.out_indptr,
              "out_indices": engine.out_indices,
              "in_indptr": engine.in_indptr, "in_indices": engine.in_indices,
              "out_deg": engine._out_deg_dev, "in_deg": engine._in_deg_dev}
    for name, a in arrays.items():
        spread = len(a.sharding.device_set)
        if spread != n_chips:
            raise SmokeFailure(f"{name} spans {spread} devices, not "
                               f"{n_chips}")
    report("graph_arrays_span_devices", n_chips)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    report("per_device_bytes_in_use", in_use)
    if None not in in_use and max(in_use) > 1.25 * min(in_use):
        raise SmokeFailure(f"per-device memory unbalanced: {in_use}")

    roots = sample_roots(deg, max_batch)
    t0 = time.perf_counter()
    engine.run_batch(roots)
    report("warmup_wave_seconds_incl_compile", time.perf_counter() - t0)
    t0 = time.perf_counter()
    rows = engine.run_batch(roots)
    secs = time.perf_counter() - t0
    report("wave_seconds", secs)
    dev = jax.devices()[0]
    report(f"aggregate_teps[{n_chips}x {dev.platform} {dev.device_kind}]",
           count_traversed_edges(deg, rows) / secs)
    checked = check_rows(ds.csr, roots[:CHECK_ROOTS], rows[:CHECK_ROOTS])
    report("oracle_roots_matched", f"{checked} of {checked}")
    report("per_device_peak_bytes_in_use",
           [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path on one chip (default); "
                         "4: only the sharded engine on a 4-chip mesh")
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache
    try:
        info = device_info()
        require_tpu(info, args.chips)
        report("compile_cache", enable_compile_cache())
        report("device", f"{info['kind']} x{info['count']}")
        if args.chips == 1:
            one_chip()
        else:
            sharded(args.chips)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
