"""Percent of the push levels' edge budget that held real arcs: the sum
over every push level of the window's waves of min(total, budget) over the
sum of their budgets, from the per-level counters the engine keeps in
``last_stats["levels"]``."""


def read(run):
    push = [lv for w in run.waves for lv in w.get("levels", ())
            if lv["mode"] == "push"]
    budget = sum(lv["budget"] for lv in push)
    if not budget:
        return None
    return 100.0 * sum(min(lv["total"], lv["budget"]) for lv in push) / budget
