"""Batched serving drivers: LM decode, and batched BFS queries (MS-BFS).

LM path: prefill a batch of prompts, then decode tokens.  The decode loop
is the same jitted ``serve_step`` the dry-run lowers at 32k/500k KV
lengths; here it runs for real on the host devices with a reduced config.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --reduced \
      --batch 4 --prompt-len 16 --gen-tokens 24

BFS path: answer a batch of BFS queries over a device-resident graph with
one multi-source traversal (``bfs_batch``) — the serving analogue of the
paper's "keep every memory channel busy" aggregate-GTEPS metric.

  PYTHONPATH=src python -m repro.launch.serve --bfs-graph rmat16-16 \
      --bfs-batch 32

Async BFS path: stream SINGLE-root queries through the dynamic batcher
(``repro.launch.dynbatch``), which coalesces everything arriving within a
window into one MS-BFS wave and reports latency percentiles + aggregate
TEPS.

  PYTHONPATH=src python -m repro.launch.serve --bfs-graph rmat16-16 \
      --bfs-serve-async --bfs-requests 64 --bfs-window 0.05 --bfs-rate 200

Other vertex programs serve through the same batcher — ``--algo cc`` /
``--algo sssp`` run batched connected components / unit-weight SSSP waves
over the same plane-packed engine, and ``--algo bfs_parents`` answers
each root with its BFS parent tree (Graph 500 kernel 2):

  PYTHONPATH=src python -m repro.launch.serve --algo cc --bfs-requests 32
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.launch.mesh import make_test_mesh
from repro.models.transformer import (init_decode_state, init_params,
                                      serve_step)
from repro.train.step import build_serve_step


def greedy_decode(arch: str, reduced: bool, batch: int, prompt_len: int,
                  gen_tokens: int, cache_len: int = 0, seed: int = 0) -> dict:
    cfg = get_reduced_config(arch) if reduced else get_config(arch)
    mesh = make_test_mesh()
    params = init_params(cfg, jax.random.key(seed))
    cache_len = cache_len or (prompt_len + gen_tokens)
    enc_len = max(prompt_len // 2, 8) if cfg.encoder_layers else 0
    caches = init_decode_state(cfg, batch, cache_len, enc_len=enc_len)
    abstract = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    fn, p_sh, c_sh = build_serve_step(
        cfg, mesh, abstract_params=abstract(params),
        abstract_caches=abstract(caches),
        abstract_tokens=jax.ShapeDtypeStruct((batch,), jnp.int32))
    params = jax.tree.map(jax.device_put, params, p_sh)
    caches = jax.tree.map(jax.device_put, caches, c_sh)

    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, (batch, prompt_len),
                          dtype=np.int32)
    # prefill = feeding prompt tokens through the decode path (tokenwise),
    # which exercises the same cache-update code the 32k cells lower.
    t0 = time.perf_counter()
    tok = jnp.asarray(prompt[:, 0])
    logits = None
    for pos in range(prompt_len):
        logits, caches = fn(params, caches, tok, jnp.int32(pos))
        tok = (jnp.asarray(prompt[:, pos + 1]) if pos + 1 < prompt_len
               else jnp.argmax(logits, -1).astype(jnp.int32))
    prefill_s = time.perf_counter() - t0

    out_tokens = [np.asarray(tok)]
    t0 = time.perf_counter()
    for pos in range(prompt_len, prompt_len + gen_tokens - 1):
        logits, caches = fn(params, caches, tok, jnp.int32(pos))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out_tokens.append(np.asarray(tok))
    jax.block_until_ready(logits)
    decode_s = time.perf_counter() - t0
    gen = np.stack(out_tokens, 1)
    return {
        "arch": cfg.name, "batch": batch, "prompt_len": prompt_len,
        "gen_tokens": gen_tokens,
        "prefill_tok_s": round(batch * prompt_len / max(prefill_s, 1e-9), 1),
        "decode_tok_s": round(batch * (gen_tokens - 1) / max(decode_s, 1e-9),
                              1),
        "sample_output": gen[0][:12].tolist(),
        "finite": bool(np.isfinite(np.asarray(logits, np.float32)).all()),
    }


def build_engine(graph, *, algo: str = "bfs",
                 distributed: bool | None = None, pes_per_device: int = 2,
                 sparse_pull: bool = False):
    """Build a vertex-program query engine with the graph device-resident.

    ``graph`` is a dataset name (``repro.graph.DATASETS``) or a
    ``repro.graph.datasets.Dataset`` the caller already generated.

    ``algo``: "bfs" | "cc" | "sssp" | "bfs_parents" (the shipped vertex
    programs — CC symmetrizes the graph first, components being an
    undirected notion; ``bfs_parents`` answers Graph 500 parent rows and
    runs on one device only: sharded parents are roadmap item R2).
    Returns (engine, out_degrees) where the degrees are those of the graph
    actually traversed (symmetrized for CC).  Single device -> the local
    runner for the program; multi-device -> ``DistributedBFS`` carrying
    the program (2 PEs per PC by default, the paper's Table II shape).
    The engine is meant to be built once and reused across ``bfs_batch``
    calls — the graph arrays stay device-resident between queries.

    ``sparse_pull=True`` enables the budgeted pull path on the local
    runners (tail pull levels expand only unvisited vertices' in-lists
    instead of scanning the whole CSC stream — the paper's actual pull
    semantics); the distributed engine ignores it for now.
    """
    from repro.core import (BFSParentsRunner, ConnectedComponentsRunner,
                            MultiSourceBFSRunner, SSSPRunner,
                            build_local_graph, get_program, partition_graph)
    from repro.graph import get_dataset, symmetrize_csr

    program = get_program(algo)
    n_dev = jax.device_count()
    if distributed is None:
        distributed = n_dev > 1
    if distributed and program.payload:
        raise ValueError(
            f"vertex program {algo!r} runs on one device only: the sharded "
            "engine combines plane words by OR and carries no payload "
            "(sharded parents are roadmap item R2)")
    ds = get_dataset(graph) if isinstance(graph, str) else graph
    csr, csc = ds.csr, ds.csc
    if program.undirected:
        csr = symmetrize_csr(csr)
        csc = csr            # a symmetrized graph is its own transpose
    deg = np.diff(csr.indptr)
    if distributed:
        from repro.compat import make_mesh
        from repro.core.bfs_distributed import DistributedBFS
        pg = partition_graph(csr, csc, n_dev * pes_per_device)
        mesh = make_mesh((n_dev,), ("data",))
        return DistributedBFS(pg, mesh, program=program), deg
    runner_cls = {"bfs": MultiSourceBFSRunner,
                  "cc": ConnectedComponentsRunner,
                  "sssp": SSSPRunner,
                  "bfs_parents": BFSParentsRunner}[algo]
    return runner_cls(build_local_graph(csr, csc),
                      sparse_pull=sparse_pull), deg


def build_bfs_engine(graph: str, *, distributed: bool | None = None,
                     pes_per_device: int = 2):
    """BFS-only compat wrapper around :func:`build_engine`."""
    return build_engine(graph, algo="bfs", distributed=distributed,
                        pes_per_device=pes_per_device)


def bfs_batch(roots, *, graph: str = "rmat16-16", engine=None,
              out_deg=None, algo: str = "bfs") -> dict:
    """Serve a batch of vertex-program queries in one batched traversal.

    ``roots``: sequence of original vertex IDs, one query each.  Duplicate
    roots are allowed (each occupies its own plane slot and resolves
    independently); negative or >= |V| roots raise ``ValueError`` — they
    would otherwise scatter silently out of bounds (every engine enforces
    this via ``repro.core.validate_roots`` in its shared entry).  Pass a
    prebuilt ``engine`` (from :func:`build_engine`) to amortize graph
    residency across calls; otherwise one is built for ``graph``/``algo``.
    Returns value rows [B, |V|] (levels / hop distances) plus aggregate
    serving stats.
    """
    from repro.core import count_traversed_edges

    if engine is None:
        engine, out_deg = build_engine(graph, algo=algo)
    # no dtype cast here: the engine validates first (a float root must
    # raise, not truncate)
    roots = np.asarray(roots)
    t0 = time.perf_counter()
    # BFSEngine protocol: every engine answers run_batch and records
    # last_stats — no more sniffing for MultiSourceBFSRunner vs distributed
    levels = engine.run_batch(roots)
    seconds = time.perf_counter() - t0      # traversal only, not stats
    stats = dict(getattr(engine, "last_stats", {}))
    traversed = stats.pop("traversed_edges", None)
    if out_deg is not None:
        traversed = count_traversed_edges(out_deg, levels)
    stats.pop("seconds", None)
    stats.pop("levels", None)       # per-level counters; the rows take the key
    stats["batch"] = int(roots.size)
    out = dict(levels=levels, seconds=round(seconds, 4), **stats)
    if traversed is not None:
        out["traversed_edges"] = traversed
        out["aggregate_teps"] = round(traversed / max(seconds, 1e-12), 1)
    return out


def serve_bfs(graph: str, batch: int, seed: int = 0,
              algo: str = "bfs") -> dict:
    engine, deg = build_engine(graph, algo=algo)
    rng = np.random.default_rng(seed)
    roots = rng.choice(np.flatnonzero(deg > 0), batch, replace=False)
    bfs_batch(roots, engine=engine, out_deg=deg)        # warm-up / compile
    out = bfs_batch(roots, engine=engine, out_deg=deg)
    levels = out.pop("levels")
    out.update(graph=graph, algo=algo,
               reached_mean=float(((levels >= 0) & (levels < (1 << 30)))
                                  .sum(1).mean()))
    return out


def serve_bfs_async(graph: str, requests: int = 64, window: float = 0.05,
                    max_batch: int = 32, rate: float | None = None,
                    seed: int = 0, algo: str = "bfs",
                    workers: int = 1, pipeline: bool = False,
                    slo: float | None = None, sparse_pull: bool = False,
                    ft_max_retries: int | None = None,
                    ft_wave_deadline: float | None = None,
                    ft_chaos: float | None = None,
                    ft_integrity: str | None = None,
                    ft_audit_rate: float = 0.05,
                    pool_evict_after: int | None = None,
                    shed: bool = False) -> dict:
    """Serve a stream of single-root queries through the dynamic batcher.

    ``rate`` (req/s) spaces submissions with exponential inter-arrival
    sleeps (open-loop Poisson); ``rate=None`` submits as fast as possible.
    ``algo`` picks the vertex program — the batcher itself is
    engine-agnostic (the ``BFSEngine`` protocol), so CC and SSSP waves
    coalesce exactly like BFS waves.

    Production-serving knobs (ROADMAP item 3): ``max_batch`` may span
    multiple plane words (e.g. 96 = three words per wave);
    ``pipeline=True`` cuts/pads wave N+1 while wave N traverses;
    ``slo`` attaches that relative deadline (seconds) to every request
    so waves cut urgency-first and ``stats()`` reports the miss rate;
    ``workers > 1`` runs a :class:`~repro.launch.pool.WorkerPool` of
    engines (sharing one device-resident graph) behind one submit
    surface, each worker supervised independently when fault tolerance
    is on.

    Fault tolerance: ``ft_max_retries`` / ``ft_wave_deadline`` wrap the
    engine in an ``EngineSupervisor`` (typed retries, quarantine
    bisection, watchdog, degradation ladder); ``ft_chaos`` additionally
    interposes a ``FaultyEngine`` injecting faults at that per-wave rate
    so the policies can be watched firing against a live stream.  With a
    supervisor, the returned stats carry a ``fault_tolerance`` block and
    failed requests resolve with typed errors instead of raising here.

    Integrity & resilience: ``ft_integrity`` picks the answer-validation
    tier (``off`` | ``invariants`` | ``witness`` | ``audit``, see
    ``repro.ft.integrity``; implies supervision), ``ft_audit_rate`` the
    sampled fraction of clean waves the ``audit`` tier re-runs through
    the reference path.  ``pool_evict_after`` sets the worker pool's
    consecutive-failure eviction threshold (``workers > 1``); ``shed``
    turns on admission control — deadline requests whose estimated queue
    delay already exceeds their SLO are refused with a typed
    ``Overloaded`` instead of queued to miss.  The returned stats then
    carry an ``integrity`` block (checks / violations / audits / sheds /
    evictions) summed across workers.

    Returns the batcher's aggregate stats (waves, mean batch, latency
    p50/p99, aggregate TEPS over busy time) as a JSON-friendly dict.
    """
    from repro.launch.dynbatch import (DynamicBatcher, drive_open_loop,
                                       plane_wave_sizes)

    if workers < 1:
        raise ValueError(f"need workers >= 1, got {workers}")
    engine, deg = build_engine(graph, algo=algo, sparse_pull=sparse_pull)
    rng = np.random.default_rng(seed)
    roots = rng.choice(np.flatnonzero(deg > 0), requests, replace=True)
    for m in plane_wave_sizes(max_batch):      # warm-up / compile
        bfs_batch(np.resize(roots, m), engine=engine, out_deg=deg)
    # extra workers share the device-resident graph; jit caches are
    # module-level so the warm-up above covers every worker's shapes
    if workers > 1 and not hasattr(engine, "g"):
        raise ValueError("workers > 1 needs local runner engines "
                         "(DistributedBFS pools are a ROADMAP item)")
    engines = [engine] + [type(engine)(engine.g, sparse_pull=sparse_pull)
                          for _ in range(workers - 1)]
    supervised = (ft_max_retries is not None or ft_wave_deadline is not None
                  or ft_chaos is not None or ft_integrity is not None)
    if supervised:
        from repro.ft import (EngineSupervisor, FaultPlan, FaultyEngine,
                              IntegrityConfig)
        integrity = (None if ft_integrity is None else
                     IntegrityConfig(mode=ft_integrity,
                                     audit_rate=ft_audit_rate))
        wrapped = []
        for i, e in enumerate(engines):
            if ft_chaos:
                # rough horizon: every request could end up a singleton
                # wave; each worker draws an independent fault schedule
                plan = FaultPlan.random(max(2 * requests, 16), ft_chaos,
                                        seed=seed + i)
                e = FaultyEngine(e, plan)
            wrapped.append(EngineSupervisor(
                e,
                max_retries=2 if ft_max_retries is None else ft_max_retries,
                wave_deadline=ft_wave_deadline,
                integrity=integrity))
        engines = wrapped
    kw = dict(out_deg=deg, window=window, max_batch=max_batch,
              pipeline=pipeline, shed=shed)
    if len(engines) > 1:
        from repro.launch.pool import WorkerPool
        if pool_evict_after is not None:
            kw["evict_after"] = pool_evict_after
        batcher = WorkerPool(engines, **kw)
    else:
        batcher = DynamicBatcher(engines[0], **kw)
    try:
        drive_open_loop(batcher, roots, rate=rate, rng=rng,
                        raise_errors=not supervised, deadline=slo,
                        allow_shed=shed)
    finally:
        out = batcher.stats()
    out.update(graph=graph, algo=algo, requests=requests, window=window,
               max_batch=max_batch, rate=rate)
    if slo is not None:
        out["slo"] = slo
    if supervised or shed:
        out["integrity"] = _integrity_summary(out)
    return out


def _integrity_summary(stats: dict) -> dict:
    """One JSON-friendly resilience rollup: integrity detector counters
    summed across workers plus the pool's shedding/eviction totals."""
    ft = stats.get("fault_tolerance")
    blocks = (ft if isinstance(ft, list) else [ft]) if ft else []
    acc = dict(checks=0, violations=0, audits=0, audit_failures=0)
    mode = "off"
    for b in blocks:
        ig = (b or {}).get("integrity")
        if not ig:
            continue
        mode = ig.get("mode", mode)
        for k in acc:
            acc[k] += int(ig.get(k, 0))
    acc["mode"] = mode
    acc["sheds"] = int(stats.get("shed", 0))
    acc["evictions"] = int(stats.get("evictions", 0))
    return acc


def main():
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--bfs-graph",
                    help="serve batched graph queries over this graph "
                         "instead of LM")
    ap.add_argument("--algo", choices=("bfs", "cc", "sssp", "bfs_parents"),
                    help="vertex program to serve (implies graph serving "
                         "through the dynamic batcher; default graph "
                         "small-12-8 when --bfs-graph is omitted)")
    ap.add_argument("--bfs-batch", type=int, default=32,
                    help="number of concurrent BFS queries")
    ap.add_argument("--bfs-serve-async", action="store_true",
                    help="serve single-root queries through the dynamic "
                         "batcher (launch.dynbatch) instead of one "
                         "pre-batched call")
    ap.add_argument("--bfs-window", type=float, default=0.05,
                    help="coalescing window in seconds (async serving)")
    ap.add_argument("--bfs-max-batch", type=int, default=32,
                    help="wave size cap = plane slots per MS-BFS wave")
    ap.add_argument("--bfs-requests", type=int, default=64,
                    help="number of single-root queries to stream (async)")
    ap.add_argument("--bfs-rate", type=float,
                    help="open-loop Poisson arrival rate in req/s "
                         "(default: submit as fast as possible)")
    ap.add_argument("--bfs-workers", type=int, default=1,
                    help="engine worker pool size (async serving; "
                         "engines share the device-resident graph)")
    ap.add_argument("--bfs-pipeline", action="store_true",
                    help="pipeline wave cutting against the engine "
                         "(cutter/dispatcher/finisher stages)")
    ap.add_argument("--bfs-slo", type=float,
                    help="attach this relative deadline (seconds) to "
                         "every request; waves cut urgency-first and "
                         "stats report the SLO miss rate")
    ap.add_argument("--bfs-sparse-pull", action="store_true",
                    help="budgeted sparse pull on tail levels (reads "
                         "only unvisited vertices' in-lists)")
    ap.add_argument("--ft-max-retries", type=int,
                    help="wrap the engine in an EngineSupervisor with this "
                         "transient-retry cap (async serving only)")
    ap.add_argument("--ft-wave-deadline", type=float,
                    help="fixed wave-watchdog deadline in seconds "
                         "(default: auto-calibrated from the running "
                         "median wave time); implies supervision")
    ap.add_argument("--ft-chaos", type=float,
                    help="inject faults at this per-wave rate through the "
                         "deterministic chaos engine (implies supervision)")
    ap.add_argument("--ft-integrity",
                    choices=("off", "invariants", "witness", "audit"),
                    help="traversal-integrity detector tier (implies "
                         "supervision): statvec invariants, sampled "
                         "witness audit, or rate-sampled differential "
                         "audit vs the reference path")
    ap.add_argument("--ft-audit-rate", type=float, default=0.05,
                    help="fraction of clean waves the audit tier re-runs "
                         "through the reference path (default 0.05)")
    ap.add_argument("--pool-evict-after", type=int,
                    help="evict a pool worker after this many consecutive "
                         "engine-failure waves (workers > 1; queued and "
                         "failing futures redispatch to survivors)")
    ap.add_argument("--shed", action="store_true",
                    help="admission control: refuse deadline requests "
                         "whose estimated queue delay already exceeds "
                         "their SLO (typed Overloaded, fails fast)")
    args = ap.parse_args()
    algo = args.algo or "bfs"
    if args.algo and not args.bfs_graph:
        args.bfs_graph = "small-12-8"
    # --algo routes through the dynamic batcher (engine-agnostic serving);
    # plain --bfs-graph keeps the one-pre-batched-call path
    if args.bfs_graph and (args.bfs_serve_async or args.algo):
        out = serve_bfs_async(args.bfs_graph, requests=args.bfs_requests,
                              window=args.bfs_window,
                              max_batch=args.bfs_max_batch,
                              rate=args.bfs_rate, algo=algo,
                              workers=args.bfs_workers,
                              pipeline=args.bfs_pipeline,
                              slo=args.bfs_slo,
                              sparse_pull=args.bfs_sparse_pull,
                              ft_max_retries=args.ft_max_retries,
                              ft_wave_deadline=args.ft_wave_deadline,
                              ft_chaos=args.ft_chaos,
                              ft_integrity=args.ft_integrity,
                              ft_audit_rate=args.ft_audit_rate,
                              pool_evict_after=args.pool_evict_after,
                              shed=args.shed)
    elif args.bfs_graph:
        out = serve_bfs(args.bfs_graph, args.bfs_batch)
    elif args.arch:
        out = greedy_decode(args.arch, args.reduced, args.batch,
                            args.prompt_len, args.gen_tokens)
    else:
        ap.error("one of --arch or --bfs-graph is required")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
