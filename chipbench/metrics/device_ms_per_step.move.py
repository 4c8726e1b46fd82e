"""Device busy time in the traced window summed over the cell's chips
(union of operations on each, from the profiler trace), divided by the
engine steps that every replica ran in it: a replica's device time a step,
whatever the number of chips.  (``device_ms_per_step.chat`` divides one
chip's busy time, and reads 1/chips of this over several replicas.)"""

from chipbench.readings import window_steps


def read(run):
    steps = window_steps(run, traced=True)
    if run.trace is None or not steps:
        return None
    return sum(run.trace.busy_s.values()) / len(steps) * 1e3
