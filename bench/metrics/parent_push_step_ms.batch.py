"""Device milliseconds per wave in the parent program's push step
(``vp_push_step_parents``)."""
from trace import program_seconds


def read(run):
    s = (program_seconds(run.trace, "vp_push_step_parents") if run.trace
         else None)
    return 1000.0 * s / len(run.waves) if s and run.waves else None
