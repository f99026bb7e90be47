"""Closed loop: ``clients`` clients, each sends its next request when the
answer to its last one arrives, while that answer comes inside the window.

A client's next request is due at the moment its last answer arrived, so
the load generator's own delay in sending it counts as latency.
"""
from __future__ import annotations

import time

from drivers import Request


def drive(batcher, traffic: dict, next_root, t0: float, seconds: float,
          deadline: float) -> list[Request]:
    t_end = t0 + seconds
    sent: list[Request] = []
    live: list[Request] = []

    def send(t_due: float):
        root = next_root()
        req = Request(root, t_due, batcher.submit(root))
        sent.append(req)
        live.append(req)

    for _ in range(int(traffic["clients"])):
        send(t0)
    while live:
        live[0].future.exception(timeout=max(deadline - time.monotonic(), 0))
        if not live[0].future.done():
            break
        done = [r for r in live if r.future.done()]
        live[:] = [r for r in live if not r.future.done()]
        for r in done:
            t_ans = r.t_answer
            if t_ans is not None and t_ans < t_end:
                send(t_ans)
    return sent
