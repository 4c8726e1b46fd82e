"""Mean host-clock time of ``ServeEngine.step`` over the steps of every
replica in the traced part of the window."""

from chipbench.readings import window_steps


def read(run):
    steps = window_steps(run, traced=True)
    return (sum(s.t1 - s.t0 for s in steps) / len(steps) * 1e3
            if steps else None)
