"""Median host-clock time of ``ServeEngine.step`` over the traced part's
steps that gave a request a slot (the program's ``Request.t_admit`` stamp
falls inside the step), minus the median over its other steps.  Medians,
since a host stall of tens of ms lands on one step in a traced part now
and then and would move a mean of its few admitting steps by more than
admission costs."""

import bisect
import statistics

from chipbench.readings import window_steps


def read(run):
    stamps = sorted(t for t in (getattr(r.request, "t_admit", None)
                                for r in run.rec.requests.values())
                    if t is not None)
    admitting, other = [], []
    for s in window_steps(run, traced=True):
        k = bisect.bisect_left(stamps, s.t0)
        held = k < len(stamps) and stamps[k] <= s.t1
        (admitting if held else other).append(s.t1 - s.t0)
    if not admitting or not other:
        return None
    return (statistics.median(admitting) - statistics.median(other)) * 1e3
