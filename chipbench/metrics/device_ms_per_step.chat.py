"""Device busy time in the traced window (union of operations, from the
profiler trace) divided by the engine steps that ran in it."""

from chipbench.readings import window_steps


def read(run):
    steps = window_steps(run, traced=True)
    if run.trace is None or not steps:
        return None
    return run.trace.mean_busy_s / len(steps) * 1e3
