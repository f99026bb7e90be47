"""MS-BFS query-engine throughput: aggregate TEPS vs concurrent batch size.

Mirrors the Fig. 9/10 scaling methodology, with the batch of concurrent BFS
queries as the scaling direction: the paper raises aggregate GTEPS by
keeping all 32 HBM pseudo-channels busy; here each extra source rides the
SAME CSR/CSC edge stream (one bit-plane per source, packed in uint32
words), so per-memory-pass useful work grows with the batch while per-
iteration edge traffic grows only with the union frontier.  Two structural
claims are validated on CPU (absolute numbers are CPU figures):

* monotonically increasing aggregate TEPS from batch=1 to batch=32, and
* the packed-word pipeline (gather/scatter-OR of uint32 plane words +
  one-sync-per-level driver) beats the legacy bool-plane path
  (``MultiSourceBFSRunner(packed=False)``) — the software re-run of the
  paper's "stream whole bitmap words per memory beat" argument.

The same harness benches the other vertex programs riding the engine
(packed arm only — the bool-plane baseline is BFS-specific):

  PYTHONPATH=src python -m benchmarks.msbfs_throughput --algo cc \
      --out BENCH_msbfs_cc.json

  PYTHONPATH=src python -m benchmarks.msbfs_throughput
  PYTHONPATH=src python -m benchmarks.msbfs_throughput \
      --out BENCH_msbfs.json --check   # CI: fail if packed is slower

The ``--use-pallas`` flag routes the packed arm through the fused Pallas
propagate kernel; at rmat20 scale the plane-array footprint exceeds the
VMEM budget, so ``kernels.ops.propagate_plan`` auto-selects the
row-tiled variant (edge stream pre-bucketed by target tile):

  PYTHONPATH=src python -m benchmarks.msbfs_throughput \
      --graph rmat20-16 --use-pallas --batches 32 --repeats 1 \
      --out BENCH_msbfs_rmat20.json --check
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from benchmarks.common import print_rows, save
from repro.core import (ConnectedComponentsRunner, MultiSourceBFSRunner,
                        SSSPRunner, SchedulerConfig, build_local_graph,
                        get_program)
from repro.graph import get_dataset, symmetrize_csr


def run(graph: str = "rmat16-16", batch_sizes=(1, 2, 4, 8, 16, 32),
        policy: str = "beamer", seed: int = 0, repeats: int = 3,
        packed_modes=(True, False), algo: str = "bfs",
        use_pallas: bool = False, tile_rows: int | None = None) -> dict:
    program = get_program(algo)
    ds = get_dataset(graph)
    csr, csc = ds.csr, ds.csc
    if program.undirected:
        csr = symmetrize_csr(csr)
        csc = csr            # a symmetrized graph is its own transpose
    g = build_local_graph(csr, csc)
    deg = np.diff(csr.indptr)
    rng = np.random.default_rng(seed)
    # roots with non-empty out-lists so every query traverses real work
    roots_all = rng.choice(np.flatnonzero(deg > 0), max(batch_sizes),
                           replace=False).astype(np.int32)
    rows = []
    for packed in packed_modes:
        sched = SchedulerConfig(policy=policy)
        # Pallas propagate (auto whole-VMEM vs row-tiled) applies to the
        # packed engine only; the bool-plane baseline stays pure jnp.
        kw = dict(use_pallas=use_pallas and packed, tile_rows=tile_rows)
        if algo == "bfs":
            runner = MultiSourceBFSRunner(g, sched, packed=packed, **kw)
        else:
            assert packed, "bool-plane baseline exists for BFS only"
            cls = {"cc": ConnectedComponentsRunner, "sssp": SSSPRunner}[algo]
            runner = cls(g, sched=sched, **kw)
        for b in batch_sizes:
            roots = roots_all[:b]
            runner.run(roots)                   # warm-up / compile
            best = None
            for _ in range(repeats):
                res = runner.run(roots)
                if best is None or res.seconds < best.seconds:
                    best = res
            rows.append(dict(
                batch=b, packed=packed, algo=algo,
                seconds=round(best.seconds, 4),
                aggregate_teps=round(best.aggregate_teps, 1),
                aggregate_gteps=round(best.gteps, 6),
                teps_per_query=round(best.aggregate_teps / b, 1),
                iterations=best.iterations,
                edges_inspected=best.edges_inspected,
                push_iters=best.push_iters, pull_iters=best.pull_iters,
                host_transfers=best.host_transfers))
    packed_rows = [r for r in rows if r["packed"]]
    # within-arm batch scaling: each arm's rows vs ITS OWN batch-1 row
    base_by_arm = {}
    for r in rows:
        base_by_arm.setdefault(r["packed"], r["aggregate_teps"])
    for r in rows:
        r["speedup_vs_b1"] = round(
            r["aggregate_teps"] / max(base_by_arm[r["packed"]], 1e-9), 2)
    out = {"graph": graph, "policy": policy, "algo": algo,
           "use_pallas": bool(use_pallas), "tile_rows": tile_rows,
           "rows": rows,
           "monotonic": all(packed_rows[i]["aggregate_teps"]
                            <= packed_rows[i + 1]["aggregate_teps"]
                            for i in range(len(packed_rows) - 1))}
    speedups = packed_speedups(rows)
    if speedups:
        out["packed_speedup"] = speedups
    return out


def packed_speedups(rows) -> dict:
    """Per-batch aggregate-TEPS ratio packed / bool-plane."""
    by = {}
    for r in rows:
        by.setdefault(r["batch"], {})[bool(r["packed"])] = r
    return {str(b): round(m[True]["aggregate_teps"]
                          / max(m[False]["aggregate_teps"], 1e-9), 2)
            for b, m in sorted(by.items()) if True in m and False in m}


def bench_record(out: dict) -> dict:
    """Stable BENCH_msbfs.json schema: graph, batch, packed, aggregate
    TEPS per row, plus the packed/bool-plane speedup map."""
    return {
        "graph": out["graph"],
        "policy": out["policy"],
        "algo": out.get("algo", "bfs"),
        "use_pallas": out.get("use_pallas", False),
        "tile_rows": out.get("tile_rows"),
        "rows": [dict(graph=out["graph"], batch=r["batch"],
                      packed=bool(r["packed"]),
                      aggregate_teps=r["aggregate_teps"])
                 for r in out["rows"]],
        "packed_speedup": out.get("packed_speedup", {}),
    }


def main():
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="rmat16-16")
    ap.add_argument("--algo", choices=("bfs", "cc", "sssp"), default="bfs",
                    help="vertex program to bench (cc/sssp run the packed "
                         "engine arm only)")
    ap.add_argument("--policy", default="beamer")
    ap.add_argument("--batches", type=int, nargs="*",
                    default=[1, 2, 4, 8, 16, 32])
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--packed-only", action="store_true",
                    help="skip the legacy bool-plane baseline arm")
    ap.add_argument("--use-pallas", action="store_true",
                    help="run the packed arm through the Pallas propagate "
                         "kernel (auto-selects whole-VMEM vs row-tiled by "
                         "plane-array footprint; see kernels.ops."
                         "propagate_plan)")
    ap.add_argument("--tile-rows", type=int, default=None,
                    help="with --use-pallas: 0 forces the whole-VMEM "
                         "kernel, >0 forces row tiles of that many "
                         "vertices (default: auto)")
    ap.add_argument("--out", metavar="PATH",
                    help="also write the stable benchmark record "
                         "(e.g. BENCH_msbfs.json at the repo root)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless the packed path is at least "
                         "as fast as the bool-plane path at every batch")
    args = ap.parse_args()
    if args.check and args.packed_only:
        ap.error("--check needs both arms; drop --packed-only")
    if args.algo != "bfs":
        if args.check:
            ap.error("--check compares against the bool-plane baseline, "
                     "which exists for --algo bfs only")
        modes = (True,)      # no bool-plane arm for cc/sssp
    else:
        modes = (True,) if args.packed_only else (True, False)
    out = run(graph=args.graph, batch_sizes=tuple(args.batches),
              policy=args.policy, repeats=args.repeats, packed_modes=modes,
              algo=args.algo, use_pallas=args.use_pallas,
              tile_rows=args.tile_rows)
    name = ("msbfs_throughput" if args.algo == "bfs"
            else f"msbfs_throughput_{args.algo}")
    save(name, out)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(bench_record(out), f, indent=2)
    print_rows("msbfs_throughput", out["rows"])
    print(f"  monotonic aggregate TEPS (packed): {out['monotonic']}")
    if out.get("packed_speedup"):
        print(f"  packed/bool-plane speedup: {out['packed_speedup']}")
    if args.check:
        speedup = out.get("packed_speedup", {})
        if not speedup:
            print("CHECK FAILED: no packed-vs-bool-plane pairs were "
                  "measured", file=sys.stderr)
            sys.exit(1)
        slow = {b: s for b, s in speedup.items() if s < 1.0}
        if slow:
            print(f"CHECK FAILED: packed path slower than bool-plane "
                  f"fallback at batches {slow}", file=sys.stderr)
            sys.exit(1)
        print("  check passed: packed >= bool-plane at every batch")


if __name__ == "__main__":
    main()
