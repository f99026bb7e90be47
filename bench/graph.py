"""The benchmark's own graph: generators, CSR build and the on-disk cache.

The generators are copies of ``repro.graph.generators`` (same random
streams, same edges), kept here so that a change to the program cannot move
the graph a cell measures.  A configuration file fixes the generator, its
parameters and the graph seed; ``--seed`` never reaches this module.

The CSR is symmetrized, self-loops dropped and duplicate arcs collapsed, as
Graph500 and GAP do.  The first run of a cell in a checkout generates it and
writes ``bench/cache/<config>-<key>/``; every later run loads it.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from pathlib import Path

import numpy as np

CACHE_DIR = Path(__file__).resolve().parent / "cache"
GENERATOR_KEYS = ("generator", "scale", "edge_factor", "a", "b", "c",
                  "graph_seed", "symmetrize")


def rmat_edges(scale: int, edge_factor: int, seed: int,
               a: float = 0.57, b: float = 0.19, c: float = 0.19):
    """Graph500 Kronecker edges: ``edge_factor * 2**scale`` (src, dst)
    int64 pairs over ``2**scale`` vertices, ids randomly permuted."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = edge_factor * n
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    d = 1.0 - a - b - c
    ab = a + b
    p_dst1_given_src0 = b / ab
    p_dst1_given_src1 = d / (c + d)
    for bit in range(scale):
        r1 = rng.random(m)
        r2 = rng.random(m)
        src_bit = r1 > ab
        dst_bit = r2 < np.where(src_bit, p_dst1_given_src1, p_dst1_given_src0)
        src |= src_bit.astype(np.int64) << bit
        dst |= dst_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    return perm[src], perm[dst]


def uniform_edges(num_vertices: int, num_edges: int, seed: int):
    """Uniform random (src, dst) int64 pairs (GAP ``urand``)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, num_vertices, size=num_edges, dtype=np.int64)
    dst = rng.integers(0, num_vertices, size=num_edges, dtype=np.int64)
    return src, dst


def csr_from_edges(src, dst, n: int, symmetrize: bool = True):
    """(indptr int64[n+1], indices int32[E]) sorted by (src, dst), without
    self-loops or duplicate arcs; each edge becomes two arcs when
    ``symmetrize``."""
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    keep = src != dst
    key = np.unique(src[keep] * n + dst[keep])
    src, dst = key // n, key % n
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return indptr, dst.astype(np.int32)


def generate(cfg: dict):
    """The configuration's CSR, generated from its graph seed."""
    n = 1 << int(cfg["scale"])
    m = int(cfg["edge_factor"]) * n
    if cfg["generator"] == "kron":
        src, dst = rmat_edges(int(cfg["scale"]), int(cfg["edge_factor"]),
                              int(cfg["graph_seed"]), cfg["a"], cfg["b"],
                              cfg["c"])
    elif cfg["generator"] == "urand":
        src, dst = uniform_edges(n, m, int(cfg["graph_seed"]))
    else:
        raise ValueError(f"unknown generator {cfg['generator']!r}")
    return csr_from_edges(src, dst, n, bool(cfg["symmetrize"]))


def cache_key(cfg: dict) -> str:
    params = {k: cfg.get(k) for k in GENERATOR_KEYS}
    blob = json.dumps(params, sort_keys=True).encode()
    return f"{cfg['name']}-{hashlib.sha256(blob).hexdigest()[:12]}"


def load_graph(cfg: dict, cache_dir: Path = CACHE_DIR):
    """Load the cached CSR or generate and cache it.

    Returns (indptr, indices, info) where info says which and how long."""
    d = cache_dir / cache_key(cfg)
    t0 = time.monotonic()
    if (d / "indices.npy").exists():
        indptr = np.load(d / "indptr.npy")
        indices = np.load(d / "indices.npy")
        return indptr, indices, {"graph_source": "cache",
                                 "graph_seconds": time.monotonic() - t0}
    indptr, indices = generate(cfg)
    gen_s = time.monotonic() - t0
    tmp = cache_dir / f".{d.name}.{os.getpid()}.tmp"
    tmp.mkdir(parents=True, exist_ok=True)
    np.save(tmp / "indptr.npy", indptr)
    np.save(tmp / "indices.npy", indices)
    os.replace(tmp, d)
    return indptr, indices, {"graph_source": "generated",
                             "graph_seconds": gen_s}
