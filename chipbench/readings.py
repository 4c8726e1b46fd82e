"""What a run recorded, and the arithmetic the metric readers share."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from chipbench import counts
from chipbench.serving import Recorder, ReqRecord, StepRecord, Window
from chipbench.spec import Cell
from chipbench.trace import Summary


@dataclasses.dataclass
class RunRecord:
    cell: Cell
    window: Window
    opened: float                    # when the window (and any trace) opened
    closed: float                    # when the window, or the traced part
                                     # of it, closed
    setup_s: float
    rec: Recorder
    trace: Optional[Summary]
    peak: Optional[Dict]             # chip peaks, None off the chip

    @property
    def model(self) -> Dict:
        return self.cell.config["model"]


def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated percentile; None for no values."""
    values = list(values)
    return float(np.percentile(values, q)) if values else None


def window_requests(run: RunRecord) -> List[ReqRecord]:
    return [r for r in run.rec.requests.values() if r.arrival.phase == "window"]


def ttfts(run: RunRecord) -> List[float]:
    """Due -> first token for every request due in the window; one still
    waiting when the run stopped enters at (stop - due)."""
    return [(r.tokens[0] if r.tokens else run.window.stop) - r.due
            for r in window_requests(run)]


def token_gaps(run: RunRecord) -> List[float]:
    """Every gap between successive tokens of a request that ends inside
    the window, for every request."""
    lo, hi = run.window.start, run.window.end
    out = []
    for r in run.rec.requests.values():
        t = r.tokens
        out.extend(b - a for a, b in zip(t, t[1:]) if lo <= b <= hi)
    return out


def window_steps(run: RunRecord, traced: bool = False) -> List[StepRecord]:
    """Steps that ran inside the scheduled window, or, with ``traced``,
    between ``opened`` and ``closed`` (the traced part of a traced run)."""
    lo, hi = ((run.opened, run.closed) if traced
              else (run.window.start, run.window.end))
    return [s for s in run.rec.steps if s.t0 >= lo and s.t1 <= hi]


def window_moves(run: RunRecord):
    lo, hi = run.window.start, run.window.end
    return [m for m in run.rec.moves if lo <= m.t_begin <= hi]


def move_gap(run: RunRecord, m) -> Optional[float]:
    """The moved session's last token on the source to its first on the
    destination; None while it has none there."""
    tokens = run.rec.requests[m.req_id].tokens
    if len(tokens) <= m.token_index:
        return None
    return tokens[m.token_index] - m.last_src_token


def traced_work(run: RunRecord):
    """(FLOPs, least seconds, steps, memory-bound steps) of the steps in
    the traced window, from the tokens each fed and their contexts."""
    flops = least = 0.0
    steps = memory_bound = 0
    for s in window_steps(run, traced=True):
        f, b = run.cell.arch.step_work(run.model, s.contexts)
        t, bound = counts.least_time(f, b, run.peak)
        flops, least = flops + f, least + t
        steps += 1
        memory_bound += bound == "memory"
    return flops, least, steps, memory_bound


def idle_share(run: RunRecord) -> Optional[float]:
    """Per cent of the traced window in which no operation ran, averaged
    over the chips."""
    if run.trace is None or not run.trace.window_s:
        return None
    return 100.0 * (1.0 - run.trace.mean_busy_s / run.trace.window_s)
