"""First-hit arcs the dense pull wrote into the parent plane, per wave:
the sum of ``hits`` over the pull levels of the window's waves, from the
per-level counters the engine keeps in ``last_stats["levels"]``, over the
number of waves.  It sets the pull's parent-write cost."""


def read(run):
    pulls = [lv for w in run.waves for lv in w.get("levels", ())
             if lv["mode"] == "pull"]
    if not run.waves or not any("hits" in lv for lv in pulls):
        return None
    return sum(lv.get("hits", 0) for lv in pulls) / len(run.waves)
