"""Distributed BFS over a device mesh (paper §IV, scaled to pods).

One mesh device == one Processing Group bound to one memory channel; each
device hosts ``k`` Processing Elements (k = shards per device), every PE
owning one contiguous (reindexed) vertex interval — level array +
visited/frontier bitmap shards live in the device's HBM, neighbor lists
stream from that HBM only (the paper's locality rule; see DESIGN.md §2).
``k`` is the paper's second scaling direction (PEs per PC, Fig. 10).

Iteration structure (python-driven, each step a jitted shard_map program):

  push:  P1 compact local frontiers (per PE) -> P2 expand local CSR
         out-lists -> DISPATCH candidates to owners (crossbar analogue)
         -> P3 receiver filters visited, updates bitmaps + levels.
  pull:  all-gather the (bit-packed) current frontier
         -> P1 compact local unvisited -> P2 expand local CSC in-lists,
         test parent frontier bits -> P3 local update (no dispatch).

Direction choice per iteration uses globally psum'd frontier statistics
(the Scheduler broadcasting its decision to all PEs).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import axis_size, shard_map
from repro.core import bitmap
from repro.core.bfs_local import (INF, SV_MF, SV_MU, SV_NF, SV_NU,
                                  SV_OVERFLOW, SV_TOTAL, compact_indices,
                                  expand_edges, validate_roots)
from repro.core.dispatcher import (or_reduce_scatter_flat,
                                   or_reduce_scatter_staged, queue_dispatch,
                                   received_to_local_bits)
from repro.core.partition import PartitionedGraph, reindex, unreindex
from repro.core.scheduler import (PULL, PUSH, SchedulerConfig, choose_mode,
                                  choose_mode_host)
from repro.core.vertex_program import BFS, VertexProgram


@dataclasses.dataclass
class DistConfig:
    dispatch: str = "bitmap"      # "bitmap" | "queue"
    crossbar: str = "staged"      # "staged" (multi-layer) | "flat" (full)
    edge_budget: int = 1 << 15    # per-shard expansion budget (auto-grows)
    queue_capacity: int = 1 << 12  # per-destination FIFO depth (queue mode)
    scheduler: SchedulerConfig = dataclasses.field(default_factory=SchedulerConfig)
    # Batched pull through the row-tiled fused propagate kernel
    # (kernels.ops.msbfs_propagate_msgs) instead of the jnp scatter-OR.
    # Pull only: the push candidates must cross the OR-reduce-scatter
    # crossbar BEFORE the visited filter, so its P3 cannot fuse into the
    # local scatter.  tile_rows=None tiles at the PE vertex interval
    # (verts_per_shard) — the partition the kernel's tiles mirror.
    use_pallas: bool = False
    tile_rows: int | None = None


class DistributedBFS:
    """Vertex-program engine over `mesh`: Q = d*k shards, k PEs per device.

    The batched path is program-parameterized (``run_program_batch``):
    the default ``program`` (BFS unless overridden at construction) keeps
    ``run_batch`` protocol-uniform, so one ``DistributedBFS(pg, mesh,
    program=CC)`` serves CC through the same ``BFSEngine`` surface.
    """

    def __init__(self, pg: PartitionedGraph, mesh: jax.sharding.Mesh,
                 axis_names: tuple[str, ...] | None = None,
                 cfg: DistConfig | None = None,
                 program: VertexProgram = BFS):
        self.pg = pg
        self.program = program
        self.mesh = mesh
        self.axes = tuple(axis_names or mesh.axis_names)
        self.axis_sizes = tuple(mesh.shape[a] for a in self.axes)
        self.cfg = cfg or DistConfig()
        q = pg.num_shards
        d = int(np.prod(self.axis_sizes))
        assert q % d == 0, f"shards {q} not a multiple of mesh size {d}"
        self.d = d
        self.k = q // d          # shards (PEs) per device (PC)
        self.q = q
        self.vl = pg.verts_per_shard          # local vertices per shard
        self.wl = self.vl // bitmap.WORD_BITS  # local bitmap words
        self.n_pad = pg.num_vertices_padded
        spec = NamedSharding(mesh, P(self.axes))
        # numpy straight onto its sharding: each device receives only its
        # own shards (staging through jnp.asarray would put every whole
        # array on one device first)
        put = lambda x: jax.device_put(np.asarray(x), spec)
        # Shard-stacked graph arrays: leading axis Q splits across devices.
        self.out_indptr = put(pg.out_indptr.astype(np.int32))
        self.out_indices = put(pg.out_indices)
        self.in_indptr = put(pg.in_indptr.astype(np.int32))
        self.in_indices = put(pg.in_indices)
        # stored per-shard degrees: the per-level scheduler stats would
        # otherwise re-derive them with jnp.diff every single iteration
        out_deg_r = np.diff(pg.out_indptr, axis=1)
        self._out_deg_dev = put(out_deg_r.astype(np.int32))
        self._in_deg_dev = put(np.diff(pg.in_indptr, axis=1).astype(np.int32))
        # original-order degrees for the engine protocol (per-wave TEPS)
        gidx = np.arange(self.n_pad)
        orig = (unreindex(gidx, q, self.vl) if pg.scheme == "hash" else gidx)
        deg = np.zeros(pg.num_vertices, np.int64)
        ok = orig < pg.num_vertices
        deg[orig[ok]] = out_deg_r.reshape(-1)[ok]
        self._out_deg_np = deg
        self._steps = {}

    @property
    def num_vertices(self) -> int:
        """|V| served (the :class:`repro.core.BFSEngine` protocol)."""
        return int(self.pg.num_vertices)

    @property
    def out_deg(self) -> np.ndarray | None:
        """Original-order out-degrees [n] (engine protocol), or None for
        ``abstract()`` spec-only engines with no materialized graph."""
        return self._out_deg_np

    @classmethod
    def abstract(cls, mesh: jax.sharding.Mesh, num_vertices: int,
                 axis_names: tuple[str, ...] | None = None,
                 cfg: DistConfig | None = None, align: int = 32,
                 pes_per_device: int = 1):
        """Spec-only engine for the multi-pod dry-run: no graph arrays are
        materialized; the jitted step programs can be .lower()ed against
        ShapeDtypeStruct inputs (see abstract_inputs)."""
        self = cls.__new__(cls)
        self.pg = None
        self.program = BFS
        self._out_deg_np = None
        self.mesh = mesh
        self.axes = tuple(axis_names or mesh.axis_names)
        self.axis_sizes = tuple(mesh.shape[a] for a in self.axes)
        self.cfg = cfg or DistConfig()
        d = int(np.prod(self.axis_sizes))
        q = d * pes_per_device
        self.d = d
        self.k = pes_per_device
        self.q = q
        vl = (num_vertices + q - 1) // q
        vl = ((vl + align - 1) // align) * align
        self.vl = vl
        self.wl = vl // bitmap.WORD_BITS
        self.n_pad = q * vl
        self._steps = {}
        return self

    def abstract_inputs(self, avg_degree: float = 16.0,
                        pad_multiple: int = 128) -> dict:
        """ShapeDtypeStruct stand-ins for one BFS step's inputs."""
        e = int(self.vl * avg_degree)
        e = max(((e + pad_multiple - 1) // pad_multiple) * pad_multiple,
                pad_multiple)
        sds = jax.ShapeDtypeStruct
        return dict(
            frontier=sds((self.q, self.wl), jnp.uint32),
            visited=sds((self.q, self.wl), jnp.uint32),
            level=sds((self.q, self.vl), jnp.int32),
            lvl=sds((), jnp.int32),
            indptr=sds((self.q, self.vl + 1), jnp.int32),
            indices=sds((self.q, e), jnp.int32),
        )

    # -- sharded state helpers -------------------------------------------
    def _sharding(self):
        return NamedSharding(self.mesh, P(self.axes))

    def init_state(self, root_reindexed: int):
        s = self._sharding()
        q, vl = self.q, self.vl
        frontier = np.zeros((q, self.wl), np.uint32)
        shard, local = root_reindexed // vl, root_reindexed % vl
        frontier[shard, local // 32] = np.uint32(1) << (local % 32)
        level = np.full((q, vl), int(INF), np.int32)
        level[shard, local] = 0
        return (jax.device_put(frontier, s),
                jax.device_put(frontier, s),                 # visited
                jax.device_put(level, s))

    # -- jitted sharded programs -----------------------------------------
    # Every shard_map block is [k, ...]: k PE rows on this device.
    def _specs(self):
        return P(self.axes)

    def _unpack_rows(self, words):
        return jax.vmap(lambda w: bitmap.unpack(w, self.vl))(words)

    def _stats_fn(self):
        axes = self.axes

        def stats(frontier, visited, out_indptr, in_indptr):
            fmask = self._unpack_rows(frontier)            # [k, vl]
            umask = ~self._unpack_rows(visited)
            odeg = jnp.diff(out_indptr, axis=1)
            ideg = jnp.diff(in_indptr, axis=1)
            n_f = jax.lax.psum(jnp.sum(fmask, dtype=jnp.int32), axes)
            m_f = jax.lax.psum(jnp.sum(jnp.where(fmask, odeg, 0),
                                       dtype=jnp.int32), axes)
            m_u = jax.lax.psum(jnp.sum(jnp.where(umask, ideg, 0),
                                       dtype=jnp.int32), axes)
            n_u = jax.lax.psum(jnp.sum(umask, dtype=jnp.int32), axes)
            return n_f, m_f, m_u, n_u

        sp = self._specs()
        return jax.jit(shard_map(
            stats, mesh=self.mesh,
            in_specs=(sp, sp, sp, sp),
            out_specs=(P(), P(), P(), P())))

    def _push_fn(self, budget: int):
        cfg, axes, sizes = self.cfg, self.axes, self.axis_sizes
        vl, wl, n_pad = self.vl, self.wl, self.n_pad
        d, k = self.d, self.k

        def push(frontier, visited, level, lvl, out_indptr, out_indices):
            fmask = self._unpack_rows(frontier)             # [k, vl]
            active = jax.vmap(lambda m: compact_indices(m, vl)[0])(fmask)
            _, nbr, valid, total = jax.vmap(
                lambda a, ip, ix: expand_edges(a, ip, ix, budget))(
                active, out_indptr, out_indices)            # nbr [k, budget]
            overflow = jax.lax.psum(
                jnp.any(total > budget).astype(jnp.int32), axes)
            nbr_flat = nbr.reshape(-1)
            if cfg.dispatch == "bitmap":
                cand_global = bitmap.from_indices_dense(nbr_flat, n_pad)
                if cfg.crossbar == "staged":
                    cand_dev = or_reduce_scatter_staged(cand_global, axes,
                                                        sizes)
                else:
                    cand_dev = or_reduce_scatter_flat(cand_global, axes, d)
                cand_local = cand_dev.reshape(k, wl)
                leftover = jnp.full((k, budget), -1, jnp.int32)
            else:
                sidx = _flat_axis_index(axes)
                recv, leftover_f = queue_dispatch(nbr_flat, axes, d, k * vl,
                                                  cfg.queue_capacity)
                cand_local = received_to_local_bits(
                    recv, sidx, k * vl).reshape(k, wl)
                leftover = leftover_f.reshape(k, budget)
            new = cand_local & ~visited
            v2 = visited | new
            new_mask = self._unpack_rows(new)
            lev2 = jnp.where(new_mask, lvl + 1, level)
            pending = jax.lax.psum(jnp.sum(leftover >= 0, dtype=jnp.int32),
                                   axes)
            return (new, v2, lev2, overflow,
                    jax.lax.psum(jnp.sum(total), axes), pending, leftover)

        sp = self._specs()
        return jax.jit(shard_map(
            push, mesh=self.mesh,
            in_specs=(sp, sp, sp, P(), sp, sp),
            out_specs=(sp, sp, sp, P(), P(), P(), sp)))

    def _queue_drain_fn(self):
        """Retry round for queue-mode overflow: dispatch leftover IDs."""
        cfg, axes = self.cfg, self.axes
        vl, wl, d, k = self.vl, self.wl, self.d, self.k

        def drain(frontier, visited, level, lvl, leftover):
            sidx = _flat_axis_index(axes)
            recv, left2 = queue_dispatch(leftover.reshape(-1), axes, d,
                                         k * vl, cfg.queue_capacity)
            cand_local = received_to_local_bits(
                recv, sidx, k * vl).reshape(k, wl)
            new = cand_local & ~visited
            v2 = visited | new
            new_mask = self._unpack_rows(new)
            lev2 = jnp.where(new_mask, lvl + 1, level)
            pending = jax.lax.psum(jnp.sum(left2 >= 0, dtype=jnp.int32),
                                   axes)
            return (frontier | new, v2, lev2, pending,
                    left2.reshape(leftover.shape))

        sp = self._specs()
        return jax.jit(shard_map(
            drain, mesh=self.mesh,
            in_specs=(sp, sp, sp, P(), sp),
            out_specs=(sp, sp, sp, P(), sp)))

    def _pull_fn(self, budget: int):
        axes, vl = self.axes, self.vl

        def pull(frontier, visited, level, lvl, in_indptr, in_indices):
            # all-gather the packed frontier (W bits total = |V|): the pull
            # mode's "read current_frontier of remote parents".
            f_global = jax.lax.all_gather(frontier, axes,
                                          tiled=True).reshape(-1)
            umask = ~self._unpack_rows(visited)
            unvisited = jax.vmap(lambda m: compact_indices(m, vl)[0])(umask)
            child, parent, valid, total = jax.vmap(
                lambda a, ip, ix: expand_edges(a, ip, ix, budget))(
                unvisited, in_indptr, in_indices)
            overflow = jax.lax.psum(
                jnp.any(total > budget).astype(jnp.int32), axes)
            hit = bitmap.test_bits(
                f_global, jnp.maximum(parent.reshape(-1), 0)
            ).reshape(parent.shape) & valid
            cand = jax.vmap(
                lambda h, c: bitmap.from_indices_dense(
                    jnp.where(h, c, -1), vl))(hit, child)
            new = cand & ~visited
            v2 = visited | new
            new_mask = self._unpack_rows(new)
            lev2 = jnp.where(new_mask, lvl + 1, level)
            return (new, v2, lev2, overflow,
                    jax.lax.psum(jnp.sum(total), axes))

        sp = self._specs()
        return jax.jit(shard_map(
            pull, mesh=self.mesh,
            in_specs=(sp, sp, sp, P(), sp, sp),
            out_specs=(sp, sp, sp, P(), P())))

    # -- batched multi-source steps (one bit-plane per source) ------------
    # State: frontier/seen uint32[q, vl, nwb] (source-mask words per local
    # vertex), level int32[q, vl, B].  Dispatch is always bitmap-mode: the
    # crossbar payload is the packed source-mask plane set and combining
    # stays a bitwise OR, so the same OR-reduce-scatter delivers a whole
    # batch per exchange (the "more concurrent work per memory pass" lever).
    #
    # Packed-word invariant: P2 gathers the packed source-mask WORDS of
    # each budgeted edge's endpoint and scatter-ORs them into the candidate
    # plane words (bitmap._scatter_or_rows — the jnp twin of the Pallas
    # msbfs_propagate kernel); plane state never unpacks between P1 and the
    # level update.  Each step also returns the NEXT level's scheduler
    # stats stacked into one replicated int32[7], so run_batch performs a
    # single blocking device->host transfer per level.

    def _ms_statvec_b(self, new, s2, odeg, ideg, total, overflow, nb: int):
        axes = self.axes
        pmask = bitmap.plane_mask(nb)
        any_f = bitmap.any_rows(new)                   # [k, vl]
        un_any = bitmap.any_rows(~s2 & pmask)
        n_f = jax.lax.psum(jnp.sum(any_f, dtype=jnp.int32), axes)
        m_f = jax.lax.psum(jnp.sum(jnp.where(any_f, odeg, 0),
                                   dtype=jnp.int32), axes)
        m_u = jax.lax.psum(jnp.sum(jnp.where(un_any, ideg, 0),
                                   dtype=jnp.int32), axes)
        n_u = jax.lax.psum(jnp.sum(un_any, dtype=jnp.int32), axes)
        cnt = jax.lax.psum(bitmap.popcount(new), axes)
        return jnp.stack([n_f, m_f, m_u, n_u,
                          jnp.asarray(total, jnp.int32),
                          jnp.asarray(overflow, jnp.int32), cnt])

    def _stats_batch_fn(self, nb: int):
        def stats_b(frontier, seen, out_deg, in_deg):
            return self._ms_statvec_b(frontier, seen, out_deg, in_deg,
                                      0, 0, nb)

        sp = self._specs()
        return jax.jit(shard_map(
            stats_b, mesh=self.mesh,
            in_specs=(sp, sp, sp, sp),
            out_specs=P()))

    def _push_batch_fn(self, budget: int, nb: int,
                       program: VertexProgram = BFS):
        cfg, axes, sizes = self.cfg, self.axes, self.axis_sizes
        vl, n_pad = self.vl, self.n_pad
        d, k = self.d, self.k
        nwb = bitmap.num_words(nb)

        def push_b(frontier, seen, level, lvl, out_indptr, out_indices,
                   out_deg, in_deg):
            any_f = bitmap.any_rows(frontier)              # [k, vl]
            active = jax.vmap(lambda m: compact_indices(m, vl)[0])(any_f)
            src, nbr, valid, total = jax.vmap(
                lambda a, ip, ix: expand_edges(a, ip, ix, budget))(
                active, out_indptr, out_indices)           # [k, budget]
            overflow = jax.lax.psum(
                jnp.any(total > budget).astype(jnp.int32), axes)
            # P2->P3 on packed words: gather each edge's source-mask word,
            # scatter-OR into the GLOBAL candidate planes (the crossbar
            # payload), no bool intermediates
            msg = jax.vmap(lambda fw, s: fw[jnp.maximum(s, 0)])(
                frontier, src)                             # [k, budget, nwb]
            tgt = jnp.where(valid, nbr, n_pad).reshape(-1)
            cand_w = bitmap._scatter_or_rows(
                jnp.zeros((n_pad, nwb), jnp.uint32), tgt,
                msg.reshape(-1, nwb)).reshape(-1)          # [n_pad * nwb]
            if cfg.crossbar == "staged":
                cand_dev = or_reduce_scatter_staged(cand_w, axes, sizes)
            else:
                cand_dev = or_reduce_scatter_flat(cand_w, axes, d)
            cand_local = cand_dev.reshape(k, vl, nwb)
            new = cand_local & ~seen
            s2 = seen | new
            new_mask = bitmap.unpack_rows(new, nb)         # program apply
            lev2 = program.commit(level, new_mask, lvl)
            statvec = self._ms_statvec_b(
                new, s2, out_deg, in_deg,
                jax.lax.psum(jnp.sum(total), axes), overflow, nb)
            return new, s2, lev2, statvec

        sp = self._specs()
        return jax.jit(shard_map(
            push_b, mesh=self.mesh,
            in_specs=(sp, sp, sp, P(), sp, sp, sp, sp),
            out_specs=(sp, sp, sp, P())))

    def _pull_batch_fn(self, budget: int, nb: int,
                       program: VertexProgram = BFS):
        axes, vl, nwb = self.axes, self.vl, bitmap.num_words(nb)
        cfg, k = self.cfg, self.k

        def pull_b(frontier, seen, level, lvl, in_indptr, in_indices,
                   out_deg, in_deg):
            # all-gather the packed source planes of every vertex: the pull
            # mode's "read current_frontier of remote parents", batched.
            f_global = jax.lax.all_gather(frontier, axes,
                                          tiled=True).reshape(-1, nwb)
            pmask = bitmap.plane_mask(nb)
            un_any = bitmap.any_rows(~seen & pmask)
            unvisited = jax.vmap(lambda m: compact_indices(m, vl)[0])(un_any)
            child, parent, valid, total = jax.vmap(
                lambda a, ip, ix: expand_edges(a, ip, ix, budget))(
                unvisited, in_indptr, in_indices)
            overflow = jax.lax.psum(
                jnp.any(total > budget).astype(jnp.int32), axes)
            # packed P2->P3: parents' plane words combine into each PE's
            # local candidate words — the gather reads the all-gathered
            # GLOBAL frontier while the scatter stays shard-local, which
            # is exactly the msgs-form fused kernel's contract
            msg = f_global[jnp.maximum(parent, 0)]         # [k, budget, nwb]
            if cfg.use_pallas:
                # row-tiled fused propagate over the k PE rows stacked
                # flat: with tile_rows = vl each kernel tile IS one PE's
                # vertex interval (the paper's PC-feeds-its-own-partition
                # rule), and P3 + the discovery popcount fuse in-kernel
                from repro.kernels import ops as kops
                offs = (jnp.arange(k, dtype=jnp.int32) * vl)[:, None]
                new_f, s2_f, _ = kops.msbfs_propagate_msgs(
                    seen.reshape(k * vl, nwb), msg.reshape(-1, nwb),
                    jnp.where(valid, child + offs, -1).reshape(-1),
                    valid.reshape(-1), tile_rows=cfg.tile_rows or vl,
                    op=program.combine)
                new = new_f.reshape(k, vl, nwb)
                s2 = s2_f.reshape(k, vl, nwb)
            else:
                cand_w = jax.vmap(
                    lambda t, m: bitmap._scatter_or_rows(
                        jnp.zeros((vl, nwb), jnp.uint32), t, m))(
                    jnp.where(valid, child, vl), msg)
                new = cand_w & ~seen
                s2 = seen | new
            new_mask = bitmap.unpack_rows(new, nb)         # program apply
            lev2 = program.commit(level, new_mask, lvl)
            statvec = self._ms_statvec_b(
                new, s2, out_deg, in_deg,
                jax.lax.psum(jnp.sum(total), axes), overflow, nb)
            return new, s2, lev2, statvec

        sp = self._specs()
        # pallas_call has no shard_map replication rule — per-shard outputs
        # here are all explicitly sharded or psum'd, so skip the check
        return jax.jit(shard_map(
            pull_b, mesh=self.mesh,
            in_specs=(sp, sp, sp, P(), sp, sp, sp, sp),
            out_specs=(sp, sp, sp, P()),
            check_vma=False if cfg.use_pallas else None))

    def _get(self, kind: str, budget: int, nb: int = 0,
             program: VertexProgram = BFS):
        key = (kind, budget, nb, program.name)
        if key not in self._steps:
            if kind == "push":
                self._steps[key] = self._push_fn(budget)
            elif kind == "pull":
                self._steps[key] = self._pull_fn(budget)
            elif kind == "stats":
                self._steps[key] = self._stats_fn()
            elif kind == "drain":
                self._steps[key] = self._queue_drain_fn()
            elif kind == "push_b":
                self._steps[key] = self._push_batch_fn(budget, nb, program)
            elif kind == "pull_b":
                self._steps[key] = self._pull_batch_fn(budget, nb, program)
            elif kind == "stats_b":
                self._steps[key] = self._stats_batch_fn(nb)
        return self._steps[key]

    def init_state_batch(self, roots_reindexed: np.ndarray):
        s = self._sharding()
        q, vl = self.q, self.vl
        b = int(roots_reindexed.size)
        nwb = bitmap.num_words(b)
        frontier = np.zeros((q, vl, nwb), np.uint32)
        level = np.full((q, vl, b), int(INF), np.int32)
        for i, r in enumerate(np.asarray(roots_reindexed)):
            shard, local = int(r) // vl, int(r) % vl
            frontier[shard, local, i // 32] |= np.uint32(1) << (i % 32)
            level[shard, local, i] = 0
        return (jax.device_put(frontier, s),
                jax.device_put(frontier, s),                 # seen
                jax.device_put(level, s))

    # -- driver -----------------------------------------------------------
    def run(self, root: int, max_iters: int | None = None):
        """BFS from original-ID ``root``; returns level int32[num_vertices]."""
        pg, cfg = self.pg, self.cfg
        if pg.scheme == "hash":
            root_r = int(reindex(np.asarray(root), pg.num_shards,
                                 pg.verts_per_shard))
        else:
            root_r = root
        frontier, visited, level = self.init_state(root_r)
        stats = self._get("stats", 0)
        budget = cfg.edge_budget
        lvl = jnp.int32(0)
        mode = jnp.int32(PUSH)
        iters = 0
        inspected = 0
        push_iters = pull_iters = 0
        max_iters = max_iters or self.n_pad
        while iters < max_iters:
            n_f, m_f, m_u, n_u = stats(frontier, visited, self.out_indptr,
                                       self.in_indptr)
            if int(n_f) == 0:
                break
            mode = choose_mode(cfg.scheduler, mode, n_f, m_f, m_u,
                               pg.num_vertices, n_u)
            is_push = int(mode) == PUSH
            need = int(m_f) if is_push else int(m_u)
            while budget * self.k < need:
                budget *= 2
            while True:
                if is_push:
                    out = self._get("push", budget)(
                        frontier, visited, level, lvl,
                        self.out_indptr, self.out_indices)
                    frontier2, visited2, level2, overflow, total = out[:5]
                    pending, leftover = out[5], out[6]
                else:
                    (frontier2, visited2, level2, overflow,
                     total) = self._get("pull", budget)(
                        frontier, visited, level, lvl,
                        self.in_indptr, self.in_indices)
                    pending = 0
                if int(overflow) == 0:
                    break
                budget *= 2            # HBM-reader queue deepening, retry
            # queue-mode FIFO overflow: extra dispatch rounds (same level).
            while int(pending) > 0:
                drain = self._get("drain", 0)
                frontier2, visited2, level2, pending, leftover = drain(
                    frontier2, visited2, level2, lvl, leftover)
            frontier, visited, level = frontier2, visited2, level2
            inspected += int(total)
            if is_push:
                push_iters += 1
            else:
                pull_iters += 1
            lvl = lvl + 1
            iters += 1
        # un-reindex levels back to original vertex order
        lev = np.asarray(level).reshape(-1)           # [q*vl] reindexed
        g = np.arange(self.n_pad)
        orig = (unreindex(g, self.q, self.vl) if pg.scheme == "hash" else g)
        out = np.full(pg.num_vertices, int(INF), np.int64)
        ok = orig < pg.num_vertices
        out[orig[ok]] = lev[ok]
        self.last_stats = dict(iterations=iters, edges_inspected=inspected,
                               push_iters=push_iters, pull_iters=pull_iters)
        return out

    def run_batch(self, roots, max_iters: int | None = None):
        """Batched vertex program from original-ID ``roots`` (the engine's
        construction-time ``program``; BFS by default).

        Returns value rows int32[B, num_vertices].  All B planes run
        level-synchronously over the same sharded graph; every CSR/CSC
        edge read and every crossbar exchange carries the whole batch's
        plane masks (bitmap dispatch only — FIFO queues carry scalar
        vertex IDs and would lose the sharing).
        """
        return self.run_program_batch(self.program, roots, max_iters)

    def run_program_batch(self, program: VertexProgram, roots,
                          max_iters: int | None = None):
        """One-sync-per-level batched driver, parameterized by program.

        The SHARED distributed entry: root validation happens here, once,
        for every algorithm.
        """
        pg, cfg = self.pg, self.cfg
        if cfg.dispatch != "bitmap":
            raise NotImplementedError(
                "run_batch supports bitmap dispatch only: FIFO queues carry "
                "scalar vertex IDs, not per-source masks")
        if program.combine != "or" or program.payload:
            raise NotImplementedError(
                "the distributed crossbar is an OR-reduce-scatter of plane "
                f"words; program {program.name!r} wants "
                f"combine={program.combine!r}, payload={program.payload!r} "
                "(sharded parents are roadmap item R2)")
        # validate BEFORE the int64 cast (a float root must error, not
        # truncate); duplicates are allowed — one plane slot each
        roots = validate_roots(np.asarray(roots),
                               pg.num_vertices).astype(np.int64)
        b = int(roots.size)
        if pg.scheme == "hash":
            roots_r = reindex(roots, pg.num_shards, pg.verts_per_shard)
        else:
            roots_r = roots
        frontier, seen, level = self.init_state_batch(roots_r)
        # one-sync-per-level driver: every step returns the next level's
        # scheduler stats as ONE replicated int32[7]; the loop's only
        # blocking device->host transfer per level is that vector.
        sv = np.asarray(self._get("stats_b", 0, b)(
            frontier, seen, self._out_deg_dev, self._in_deg_dev))
        budget = cfg.edge_budget
        mode = PUSH
        iters = 0
        inspected = 0
        push_iters = pull_iters = 0
        max_iters = max_iters or self.n_pad
        while iters < max_iters and not program.done(sv):
            mode = choose_mode_host(cfg.scheduler, mode, int(sv[SV_NF]),
                                    int(sv[SV_MF]), int(sv[SV_MU]),
                                    pg.num_vertices, int(sv[SV_NU]))
            is_push = mode == PUSH
            need = int(sv[SV_MF]) if is_push else int(sv[SV_MU])
            # ``budget`` is per shard and ``need`` spans all q shards: start
            # at an even share, and let overflow deepen a skewed shard
            while budget * self.q < need:
                budget *= 2
            while True:
                kind = "push_b" if is_push else "pull_b"
                arrays = ((self.out_indptr, self.out_indices) if is_push
                          else (self.in_indptr, self.in_indices))
                (frontier2, seen2, level2, statvec) = self._get(
                    kind, budget, b, program)(
                    frontier, seen, level, np.int32(iters), *arrays,
                    self._out_deg_dev, self._in_deg_dev)
                sv = np.asarray(statvec)
                if int(sv[SV_OVERFLOW]) == 0:
                    break
                budget *= 2            # HBM-reader queue deepening, retry
            frontier, seen, level = frontier2, seen2, level2
            inspected += int(sv[SV_TOTAL])
            if is_push:
                push_iters += 1
            else:
                pull_iters += 1
            iters += 1
        lev = np.asarray(level).reshape(-1, b)        # [q*vl, B] reindexed
        g = np.arange(self.n_pad)
        orig = (unreindex(g, self.q, self.vl) if pg.scheme == "hash" else g)
        out = np.full((b, pg.num_vertices), int(INF), np.int64)
        ok = orig < pg.num_vertices
        out[:, orig[ok]] = lev[ok].T
        self.last_stats = dict(iterations=iters, edges_inspected=inspected,
                               push_iters=push_iters, pull_iters=pull_iters,
                               batch=b, algo=program.name)
        return out


def _flat_axis_index(axes: tuple[str, ...]) -> jax.Array:
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * axis_size(a) + jax.lax.axis_index(a)
    return idx
