"""Process start to the first timed request: JAX start-up, weights,
compiling or loading each bucket's wave program, and one warm wave per
bucket."""


def read(run):
    return run.setup_s
