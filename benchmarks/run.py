"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run              # everything
  PYTHONPATH=src python -m benchmarks.run --only fig8  # one benchmark
  PYTHONPATH=src python -m benchmarks.run --quick      # small graphs only

Results print as CSV blocks and are saved under experiments/bench/.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (fig7_perf_model, fig8_hybrid_modes, fig9_pc_scaling,
                        fig10_pe_scaling, fig11_partitioning,
                        msbfs_throughput, roofline_report, table3_real_graphs)
from benchmarks.common import print_rows, save

BENCHES = {
    "fig7": ("perf model Eq.1-7 / Fig.7 curves + crossbar math",
             lambda quick: fig7_perf_model.run()),
    "fig8": ("hybrid vs push vs pull GTEPS (Fig.8)",
             lambda quick: fig8_hybrid_modes.run(
                 graphs=("rmat18-8", "rmat18-16") if quick
                 else fig8_hybrid_modes.GRAPHS)),
    "fig9": ("PC (device) scaling (Fig.9)",
             lambda quick: fig9_pc_scaling.run(
                 device_counts=(1, 2, 4) if quick else (1, 2, 4, 8))),
    "fig10": ("PEs per PC scaling (Fig.10)",
              lambda quick: fig10_pe_scaling.run(
                  graphs=("rmat18-8",) if quick
                  else ("rmat18-8", "rmat18-64"),
                  pes=(1, 2, 4) if quick else (1, 2, 4, 8))),
    "fig11": ("hash vs contiguous placement (Fig.11)",
              lambda quick: fig11_partitioning.run(
                  graphs=("rmat18-16",) if quick
                  else ("rmat18-16", "lj-like"))),
    "msbfs": ("MS-BFS aggregate TEPS vs concurrent batch size",
              lambda quick: msbfs_throughput.run(
                  graph="rmat14-8" if quick else "rmat16-16",
                  batch_sizes=(1, 4, 16) if quick else (1, 2, 4, 8, 16, 32))),
    "table3": ("real-world graph throughput (Table III)",
               lambda quick: table3_real_graphs.run()),
    "roofline": ("dry-run roofline aggregation (§Roofline)",
                 lambda quick: roofline_report.run()),
}


def main() -> int:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    names = args.only or list(BENCHES)
    failures = 0
    for name in names:
        desc, fn = BENCHES[name]
        print(f"\n=== {name}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            out = fn(args.quick)
        except Exception:
            traceback.print_exc()
            failures += 1
            continue
        out["bench_seconds"] = round(time.time() - t0, 1)
        save(name, out)
        rows = out.get("rows", [])
        print_rows(name, rows)
        for k, v in out.items():
            if k not in ("rows", "bfs_rows"):
                print(f"  {k}: {v}" if not isinstance(v, (list, dict))
                      else f"  {k}: {str(v)[:200]}")
        print(f"  [{time.time()-t0:.1f}s]", flush=True)
    return failures


if __name__ == "__main__":
    sys.exit(main())
