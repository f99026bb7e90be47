"""Percent of HBM bandwidth for parent trees: the least bytes of the traced
waves over their device busy time times the chip's HBM peak from
``bench/peaks.json``.  The least bytes are ``readings.least_bytes`` over
the levels read off the parent rows (``trees.levels_from_parents``), plus
4 B for the parent written at each reached (vertex, request)."""
import numpy as np

from readings import least_bytes, wave_rows
from trees import levels_from_parents


def read(run):
    t = run.trace
    if t is None or not t["busy_s"] or run.peaks is None:
        return None
    need = 0
    for slots, rows in wave_rows(run):
        rows = np.stack([np.asarray(r) for r in rows])
        need += least_bytes(run, list(levels_from_parents(rows)), slots)
        need += 4 * int(np.count_nonzero(rows >= 0))
    return 100.0 * need / (t["busy_s"] * float(run.peaks["hbm_bytes_per_s"]))
