"""Distributed-BFS example: the paper's Table II configurations, scaled to
however many host devices exist, with both dispatcher designs.

Shows the full/multi-layer crossbar trade-off the paper measures
(§IV-D): flat = one all-to-all over all devices; staged = one exchange
per mesh axis (the k-layer crossbar).

  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/distributed_bfs.py
"""
import time

import numpy as np
import jax

from repro.compat import make_mesh
from repro.core import bfs_oracle, count_traversed_edges, partition_graph
from repro.core.bfs_distributed import DistConfig, DistributedBFS
from repro.core.perf_model import (full_crossbar_fifos,
                                   multilayer_crossbar_fifos)
from repro.graph import get_dataset


def main():
    n_dev = jax.device_count()
    dev = jax.devices()[0]
    device = f"{dev.platform} {dev.device_kind}"   # labels every rate
    ds = get_dataset("rmat18-16")
    deg = np.diff(ds.csr.indptr)
    root = int(np.argmax(deg))
    oracle = np.minimum(bfs_oracle(ds.csr, root), 1 << 30)

    # 2 PEs per PC, the paper's 32PC/64PE shape (scaled to n_dev PCs)
    q = n_dev * 2
    pg = partition_graph(ds.csr, ds.csc, q)
    if n_dev >= 4:
        mesh = make_mesh((n_dev // 2, 2), ("data", "model"))
    else:
        mesh = make_mesh((n_dev,), ("data",))
    print(f"devices={n_dev} mesh={dict(mesh.shape)} shards={q} (2 PEs/PC)")

    for dispatch, crossbar in (("bitmap", "flat"), ("bitmap", "staged"),
                               ("queue", "flat")):
        eng = DistributedBFS(pg, mesh, cfg=DistConfig(
            dispatch=dispatch, crossbar=crossbar))
        lev = eng.run(root)          # warm-up + correctness
        assert np.array_equal(np.minimum(lev, 1 << 30), oracle)
        t0 = time.perf_counter()
        eng.run(root)
        dt = time.perf_counter() - t0
        trav = int(deg[np.minimum(lev, 1 << 30) < (1 << 30)].sum())
        print(f"  {dispatch:6s}/{crossbar:6s}: ok, {dt:.2f}s, "
              f"{trav/dt/1e9:.4f} GTEPS ({device}), {eng.last_stats}")

    print("crossbar resource model (paper §IV-D):",
          f"64x64 full = {full_crossbar_fifos(64)} FIFOs,",
          f"3-layer 4x4 = {multilayer_crossbar_fifos((4, 4, 4))} FIFOs")

    # batched MS-BFS: 32 concurrent queries share every edge read and every
    # crossbar exchange (one bit-plane per source) — the aggregate-GTEPS
    # serving mode.  Also reachable via repro.launch.serve.bfs_batch.
    rng = np.random.default_rng(0)
    roots = rng.choice(np.flatnonzero(deg > 0), 32, replace=False)
    eng = DistributedBFS(pg, mesh, cfg=DistConfig(dispatch="bitmap",
                                                  crossbar="flat"))
    levels = eng.run_batch(roots)          # warm-up + correctness
    for i, r in enumerate(roots[:4]):      # spot-check vs per-root oracle
        assert np.array_equal(np.minimum(levels[i], 1 << 30),
                              np.minimum(bfs_oracle(ds.csr, int(r)), 1 << 30))
    t0 = time.perf_counter()
    levels = eng.run_batch(roots)
    dt = time.perf_counter() - t0
    trav = count_traversed_edges(deg, levels)
    print(f"  MS-BFS batch=32: ok, {dt:.2f}s, {trav/dt/1e9:.4f} aggregate "
          f"GTEPS ({device}), {eng.last_stats}")


if __name__ == "__main__":
    main()
