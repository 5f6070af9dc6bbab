"""Process start to the first instant of the window: JAX start, native
build, files, weights, server, index fill, corpus, warm-up, settling."""


def read(run):
    return run.extras["setup_s"]
