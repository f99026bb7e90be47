"""Quantities several metric readers share, computed from one run."""
from __future__ import annotations

import numpy as np

from reference import INF


def latencies(run) -> np.ndarray:
    """Seconds from when each request of the window was due to its answer;
    a request never answered counts until the run stopped waiting."""
    return np.asarray([(r.t_answer if r.t_answer is not None
                        and r.future.exception() is None else run.t_close)
                       - r.t_due for r in run.requests], np.float64)


def wave_rows(run) -> list[tuple[int, list]]:
    """(plane slots, level rows of its answered requests) per served wave."""
    waves: dict[int, tuple[int, list]] = {}
    for r in run.answered:
        w = r.future.wave
        waves.setdefault(id(w), (w.n_slots, []))[1].append(r.row)
    return list(waves.values())


def least_bytes(run, rows: list, slots: int) -> int:
    """Bytes any implementation of one wave must move.

    At each level L: 4 B for each of min(m_f, m_u) arcs, where m_f is the
    arcs out of the vertices some request reaches at level L and m_u the
    arcs into the vertices some request has not reached before L, plus one
    read and one write of the level's packed plane words (4 B per vertex
    per 32 planes).  The same work counts the same whatever implements the
    step: push, pull, sparse pull or a kernel."""
    levels = np.stack([np.asarray(r) for r in rows])
    deg = np.asarray(run.deg, np.int64)
    latest = levels.max(axis=0)                     # INF where any missed
    top = int(levels[levels < INF].max(initial=0))
    words = -(-slots // 32)
    plane_bytes = 2 * 4 * words * levels.shape[1]
    total = 0
    for lvl in range(top + 1):
        m_f = int(deg[(levels == lvl).any(axis=0)].sum())
        m_u = int(deg[latest > lvl].sum())
        total += 4 * min(m_f, m_u) + plane_bytes
    return total
