"""Percent of HBM bandwidth: the least bytes of the traced waves
(``readings.least_bytes``) over their device busy time times the chip's
HBM peak from ``bench/peaks.json``."""
from readings import least_bytes, wave_rows


def read(run):
    t = run.trace
    if t is None or not t["busy_s"] or run.peaks is None:
        return None
    need = sum(least_bytes(run, rows, slots) for slots, rows in wave_rows(run))
    return 100.0 * need / (t["busy_s"] * float(run.peaks["hbm_bytes_per_s"]))
