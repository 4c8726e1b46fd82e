"""Mean, over the requests given a slot inside the window that got their
first token before the run stopped, of the program's own stamps
``t_first - t_admit`` (on ``repro.serve.Request``): the prompt's feed.  As
for ``ttft_p90_s``, the first token may come after the window, or its end
would leave out the long prompts.  Where the interval holds ``closed``, at
which a traced run's profiler stops and stalls the host for tens of
seconds, that stall (from the last step that ended before ``closed`` to
the first that started after it) is taken off."""


def read(run):
    lo, hi = run.window.start, run.window.end
    before = [s.t1 for s in run.rec.steps if s.t1 <= run.closed]
    after = [s.t0 for s in run.rec.steps if s.t0 >= run.closed]
    stall = min(after) - max(before) if before and after else 0.0
    feeds = []
    for r in run.rec.requests.values():
        admit = getattr(r.request, "t_admit", None)
        first = getattr(r.request, "t_first", None)
        if admit is None or first is None or not lo <= admit <= hi:
            continue
        feeds.append(first - admit
                     - (stall if admit <= run.closed <= first else 0.0))
    return sum(feeds) / len(feeds) if feeds else None
