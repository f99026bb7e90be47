"""Traversed edges of every request answered, over the seconds from the
window's start to the last answer (paper §VI-A, Graph500, GAP)."""


def read(run):
    if run.t_last is None or run.t_last <= run.t_window:
        return None
    return sum(r.traversed for r in run.answered) / (run.t_last - run.t_window)
