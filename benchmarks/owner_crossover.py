"""Time ``expand_edges`` on each owner path, to place ``OWNER_MERGE_RATIO``.

For each budget rung, ``expand_edges`` runs over an active list of ``n``
slots (a quarter of them, at most budget / 16, real vertices of degree
0..22; the rest ``-1`` pads, as ``compact_indices`` leaves them) once with
every slot's owner found by the binary search and once by the merge.  The
path is held by the module's crossover constant, so both runs are the
program's own code.  Each time is the mean of ``--reps`` calls after one
warm call.  Both paths must give the same four outputs, or the script
fails.

Run it on the device it measures (the times mean nothing on the CPU):

  PYTHONPATH=src python -m benchmarks.owner_crossover \
      --log-n 22 --budgets 15-23 --out owner_crossover.json
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.bfs_local as bl

PATHS = {"search": 0, "merge": 1 << 62}     # OWNER_MERGE_RATIO per path


def _time(fn, args, reps: int) -> tuple[float, list]:
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(1e3 * (time.perf_counter() - t0))
    return float(np.mean(ts)), ts


def main(argv=None) -> list[dict]:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--log-n", type=int, default=22)
    ap.add_argument("--budgets", default="15-23",
                    help="log2 budget range, inclusive")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--out")
    a = ap.parse_args(argv)
    n = 1 << a.log_n
    lo, hi = (int(x) for x in a.budgets.split("-"))
    rng = np.random.default_rng(a.seed)
    deg = rng.integers(0, 23, size=n)
    indptr = jnp.asarray(np.concatenate([[0], np.cumsum(deg)]), jnp.int32)
    indices = jnp.asarray(rng.integers(0, n, size=int(deg.sum())),
                          jnp.int32)
    dev = jax.devices()[0]
    rows = []
    ratio0 = bl.OWNER_MERGE_RATIO
    try:
        for lb in range(lo, hi + 1):
            rows.append(_rung(1 << lb, n, rng, indptr, indices, a.reps))
    finally:
        bl.OWNER_MERGE_RATIO = ratio0
    result = dict(device=dev.device_kind, platform=dev.platform, rows=rows)
    if a.out:
        with open(a.out, "w") as f:
            json.dump(result, f, indent=1)
    return rows


def _rung(budget: int, n: int, rng, indptr, indices, reps: int) -> dict:
    """Both paths at one budget; the path is held by the module constant
    while ``expand_edges`` is traced."""
    k = min(n // 4, max(32, budget // 16))
    act = np.full(n, -1, np.int32)
    act[:k] = np.sort(rng.choice(n, size=k, replace=False))
    active = jnp.asarray(act)
    outs, row = {}, dict(budget=budget, n=n, active=k)
    for path, ratio in PATHS.items():
        bl.OWNER_MERGE_RATIO = ratio
        fn = jax.jit(lambda x, p, i: bl.expand_edges(x, p, i, budget))
        row[f"{path}_ms"], row[f"{path}_runs"] = _time(
            fn, (active, indptr, indices), reps)
        outs[path] = [np.asarray(x) for x in fn(active, indptr, indices)]
    for x, y in zip(outs["search"], outs["merge"]):
        np.testing.assert_array_equal(x, y)
    row["total"] = int(outs["search"][3])
    print(json.dumps({k: v for k, v in row.items()
                      if not k.endswith("_runs")}), flush=True)
    return row


if __name__ == "__main__":
    main()
