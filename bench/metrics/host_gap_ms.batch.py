"""Mean device-clock gap, in ms per level, between a wave's consecutive
step programs: the statvec fetch, the host's direction choice and the
next dispatch."""
from trace import step_gaps


def read(run):
    if run.trace is None:
        return None
    gaps = step_gaps(run.trace["modules"])
    return 1000.0 * sum(gaps) / len(gaps) if gaps else None
