"""Sum over the traced window's decode steps of the least time each needs
(the larger of FLOPs / peak and bytes / HBM bandwidth, counted from the
tokens and contexts it processed) over the device's busy time."""

import sys

from chipbench.readings import traced_work


def read(run):
    if run.trace is None or run.peak is None or not run.trace.mean_busy_s:
        return None
    _, least, steps, memory_bound = traced_work(run)
    if not steps:
        return None
    print(f"decode_roofline: memory-bound on {memory_bound} of {steps} steps",
          file=sys.stderr)
    return 100.0 * least / run.trace.mean_busy_s
