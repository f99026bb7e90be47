"""Load generators, one module per traffic ``driver`` kind.

Each module defines ``drive(batcher, traffic, next_root, t0, seconds,
deadline)``: it submits requests due in ``[t0, t0 + seconds)`` on the
``time.monotonic`` clock and returns one :class:`Request` per submission.
It does not wait past ``deadline`` for an answer.
"""
from __future__ import annotations

import dataclasses
import importlib


@dataclasses.dataclass
class Request:
    root: int
    t_due: float          # when the request was due to be sent
    future: object        # the batcher's BFSFuture

    @property
    def t_answer(self) -> float | None:
        f = self.future
        if not f.done() or f.latency is None:
            return None
        return f.t_submit + f.latency


def load(kind: str):
    if not kind.replace("_", "").isalnum():
        raise ValueError(f"bad driver name {kind!r}")
    return importlib.import_module(f"drivers.{kind}")
