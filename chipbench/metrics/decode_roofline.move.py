"""Sum over every replica's decode steps in the traced window of the least
time each needs (the larger of FLOPs / peak and bytes / HBM bandwidth,
counted from the tokens and contexts it processed) over the device's busy
time summed over the cell's chips.  (``decode_roofline.chat`` divides by
one chip's busy time, and reads chips times this over several replicas.)"""

import sys

from chipbench.readings import traced_work


def read(run):
    if run.trace is None or run.peak is None:
        return None
    busy = sum(run.trace.busy_s.values())
    _, least, steps, memory_bound = traced_work(run)
    if not busy or not steps:
        return None
    print(f"decode_roofline: memory-bound on {memory_bound} of {steps} steps",
          file=sys.stderr)
    return 100.0 * least / busy
