"""Process start to window start: JAX start-up, graph load or generation,
warm-up compiles and the engine build."""


def read(run):
    return run.setup_s
