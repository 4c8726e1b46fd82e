"""Process start -> window start: loading, weights, compiles from the
cache, warm-up and the traffic that brings the engines to a steady load."""


def read(run):
    return run.setup_s
