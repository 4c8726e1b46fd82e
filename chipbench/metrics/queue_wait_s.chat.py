"""Mean, over the requests given a slot in the traced part of the window,
of due -> the start of the step that gave them one.  (A traced run's
profiler stops when that part closes, and the stall of writing the trace
would otherwise fall on the requests due in its last step.)"""


def read(run):
    waits = [r.admit - r.due for r in run.rec.requests.values()
             if r.admit is not None and run.opened <= r.admit <= run.closed]
    return sum(waits) / len(waits) if waits else None
