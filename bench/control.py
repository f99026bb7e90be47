#!/usr/bin/env python3
"""Read both ends of each answer check's limit on the chip, at a cell's size.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <a> <b> ...

For each seed, one short run of the cell's timed path (``run.run_cell``:
the same graph, warm-up, engine, batcher and load) gives the program's
readings: the checks of the configuration's output module
(``bench/outputs/<output>.py``) over the sampled answers.  The output's
``control``, the shortcut that must fail them (for levels a push that drops
the arcs beyond a fixed budget instead of re-running the level; for parents
a sibling adopted as parent), is then put in the program's place for the
same roots and checked the same way, which gives the other reading.  One
JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    manifest = run.load_manifest()
    cell, cfg, traffic = run.cell_inputs(manifest, args.workload)
    output = run.load_output(cfg["output"])
    for seed in args.seeds:
        keep: dict = {}
        out = run.run_cell(cell, cfg, traffic, [], seed, args.seconds, False,
                           t_process=time.monotonic(), keep=keep,
                           log=lambda *a: None)
        t0 = time.monotonic()
        graph = (keep["indptr"], keep["indices"])
        ctl = output.control(*graph, keep["roots"])
        ctl_checks = output.check(*graph, keep["roots"], ctl)
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "program": {k: out["checks"][k]["value"] for k in ctl_checks},
            "control": {k: c["value"] for k, c in ctl_checks.items()},
            "rows_checked": len(keep["roots"]),
            "control_seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
