"""Root sequences named by a traffic mix's ``roots.kind``.

Every sampler draws from the vertices of nonzero degree, as GAP's BFS
benchmark and Graph500 pick their search keys.  The sequence of roots comes
from the mix's ``pool_seed``, so every run serves the same roots in the same
waves; ``--seed`` permutes them within consecutive blocks of
``shuffle_block``.  A wave's time depends on its roots (they set how many
levels it runs and how wide each level's frontier is, and so each push
level's edge budget), so roots drawn afresh for each seed would change the
work from run to run.
"""
from __future__ import annotations

import numpy as np


def uniform(spec: dict, deg: np.ndarray, rng: np.random.Generator):
    """Uniform over the vertices of nonzero degree."""
    nz = np.flatnonzero(deg > 0)
    return lambda k: nz[rng.integers(nz.size, size=k)]


def zipf(spec: dict, deg: np.ndarray, rng: np.random.Generator):
    """Zipf with exponent ``s`` over a permutation of the vertices of
    nonzero degree: rank r is drawn with weight r**-s."""
    nz = rng.permutation(np.flatnonzero(deg > 0))
    cdf = np.cumsum(np.arange(1, nz.size + 1, dtype=np.float64)
                    ** -float(spec["s"]))
    return lambda k: nz[np.minimum(
        np.searchsorted(cdf, rng.random(k) * cdf[-1], side="right"),
        nz.size - 1)]


SAMPLERS = {"uniform": uniform, "zipf": zipf}


def make_sampler(spec: dict, deg: np.ndarray, seed: int):
    """``next_root()`` over the mix's root sequence, shuffled by ``seed``."""
    if spec["kind"] not in SAMPLERS:
        raise ValueError(f"unknown root sampler {spec['kind']!r}; "
                         f"have {sorted(SAMPLERS)}")
    draw = SAMPLERS[spec["kind"]](spec, deg,
                                  np.random.default_rng(spec["pool_seed"]))
    order = np.random.default_rng(seed)
    block = int(spec["shuffle_block"])
    pending: list[int] = []

    def next_root() -> int:
        if not pending:
            pending.extend(order.permutation(draw(block)).tolist()[::-1])
        return int(pending.pop())

    return next_root
