"""Open loop: Poisson arrivals at ``rate_per_s``.

The arrival times come from the mix's own ``arrival_seed``, so every run
offers the same load at the same moments; ``--seed`` draws only which root
each arrival asks for.  Latency runs from when a request was due.
"""
from __future__ import annotations

import time

import numpy as np

from drivers import Request


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    rate = float(traffic["rate_per_s"])
    rng = np.random.default_rng(int(traffic["arrival_seed"]))
    gaps = rng.exponential(1.0 / rate, size=int(rate * seconds * 2) + 16)
    at = np.cumsum(gaps)
    return at[at < seconds]


def drive(batcher, traffic: dict, next_root, t0: float, seconds: float,
          deadline: float) -> list[Request]:
    sent = []
    for off in arrivals(traffic, seconds):
        due = t0 + float(off)
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        root = next_root()
        sent.append(Request(root, due, batcher.submit(root)))
    return sent
