"""Process start to window open: JAX start, weights, engine, compiles or
cache loads, shape warm-up and the warm traffic."""


def read(run):
    return run.setup_s
