"""Median milliseconds a request waited in the batcher before its wave
was cut (``BFSFuture.wave.t_start - t_submit``), over answered requests."""
import numpy as np


def read(run):
    waits = [r.future.wave.t_start - r.future.t_submit for r in run.answered]
    return 1000.0 * float(np.median(waits)) if waits else None
