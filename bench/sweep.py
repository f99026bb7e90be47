#!/usr/bin/env python3
"""Find the knee of an open-loop cell: its rate swept over fixed values.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1.2 1.6 ...

One run of the cell per rate, in one process, with the mix's
``rate_per_s`` replaced.  Each prints one JSON line: requests sent and
answered, latency p50/p90, the slope of latency against due time (about 0
below the knee, positive when the backlog grows through the window) and
how long the drain took after the window closed.  The knee is the highest
rate whose backlog does not grow; the cell's mix is set at 4/5 of it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    manifest = run.load_manifest()
    cell, cfg, traffic = run.cell_inputs(manifest, args.workload)
    metrics = run.cell_metrics(manifest, args.workload, False)
    for rate in args.rates:
        keep: dict = {}
        out = run.run_cell(cell, cfg, dict(traffic, rate_per_s=rate), metrics,
                           args.seed, args.seconds, False,
                           t_process=time.monotonic(), keep=keep,
                           log=lambda *a: None)
        due, lat = np.asarray(keep["latencies"]).T
        print(json.dumps({
            "rate_per_s": rate, "correct": out["correct"],
            "sent": out["attempted"], "failed": out["failed"],
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p90_s": float(np.percentile(lat, 90)),
            "latency_slope": float(np.polyfit(due, lat, 1)[0]),
            "drain_s": float(max(due + lat) - args.seconds),
            "teps": out["metrics"].get("teps", {}).get("value")}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
