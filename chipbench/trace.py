"""From a profiler trace to device busy time, idle gaps and their causes.

``read_xplane`` reads JAX's ``.xplane.pb`` into a ``Trace``: the harness's
host annotations as ``Event`` rows, and each device's operations as arrays
(a traced second of a decode loop holds hundreds of thousands of them).
The reduction works on a ``Trace`` alone, so a short recorded cut of one,
kept with ``save``, tests it.

Device planes are ``/device:<kind>:<n>``; their ``XLA Ops`` line holds one
event per operation run, control-flow operations (a layer loop's ``while``)
spanning the operations they run.  Busy time is the union of those
intervals inside the window, which is the span of the host annotation
``chipbench.window``.  Each idle gap on a device is put down to the host
annotation (``engine.step``, ``move.export``, ``move.import``,
``harness.*``) in flight at its middle: the one of the thread that drives
that device, as its ``replica`` stat says, or else the harness's own.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("engine.", "move.", "harness.", WINDOW)
CONTAINERS = ("while", "conditional", "call")     # hold other operations
OUTSIDE = "host.outside_annotations"
_DEVICE = re.compile(r"^/device:[A-Z]+:(\d+)$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    replica: Optional[int] = None


@dataclasses.dataclass
class DeviceOps:
    names: List[str]              # distinct operation names
    name_idx: np.ndarray          # per operation, into ``names``
    start_ns: np.ndarray
    dur_ns: np.ndarray


@dataclasses.dataclass
class Trace:
    host: List[Event]
    devices: Dict[int, DeviceOps]


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def read_xplane(path) -> Trace:
    from jax.profiler import ProfileData     # needs only JAX's own reader
    data = ProfileData.from_file(str(path))
    host, devices = [], {}
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                index: Dict[str, int] = {}
                idx, start, dur = [], [], []
                for e in line.events:
                    idx.append(index.setdefault(op_name(e.name), len(index)))
                    start.append(e.start_ns)
                    dur.append(e.duration_ns)
                devices[int(m.group(1))] = DeviceOps(
                    list(index), np.asarray(idx, np.int32),
                    np.asarray(start, np.float64), np.asarray(dur, np.float64))
            elif not m:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        replica = dict(e.stats).get("replica")
                        host.append(Event(e.name, float(e.start_ns),
                                          float(e.duration_ns),
                                          None if replica is None
                                          else int(replica)))
    return Trace(host, devices)


def save(trace: Trace, path) -> None:
    """Gzipped JSON, the device operations as columns."""
    body = {"host": [dataclasses.astuple(e) for e in trace.host],
            "devices": {str(d): {"names": ops.names,
                                 "name_idx": ops.name_idx.tolist(),
                                 "start_ns": ops.start_ns.tolist(),
                                 "dur_ns": ops.dur_ns.tolist()}
                        for d, ops in trace.devices.items()}}
    with gzip.open(path, "wt") as f:
        json.dump(body, f)


def load(path) -> Trace:
    with gzip.open(path, "rt") as f:
        body = json.load(f)
    return Trace([Event(*row) for row in body["host"]],
                 {int(d): DeviceOps(o["names"],
                                    np.asarray(o["name_idx"], np.int32),
                                    np.asarray(o["start_ns"], np.float64),
                                    np.asarray(o["dur_ns"], np.float64))
                  for d, o in body["devices"].items()})


def union(start: np.ndarray, end: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Disjoint sorted intervals covering the given ones."""
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    start, end = start[order], np.maximum.accumulate(end[order])
    new = np.concatenate([[True], start[1:] > end[:-1]])
    last = np.concatenate([new[1:], [True]])
    return start[new], end[last]


def idle(start: np.ndarray, end: np.ndarray, lo: float,
         hi: float) -> Tuple[np.ndarray, np.ndarray]:
    """Gaps of disjoint sorted intervals inside [lo, hi]."""
    gap_lo = np.concatenate([[lo], end])
    gap_hi = np.concatenate([start, [hi]])
    keep = gap_hi > gap_lo
    return gap_lo[keep], gap_hi[keep]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: Dict[int, float]                  # by device index
    device_ops: List[Tuple[str, float]]       # most time first
    idle_gaps: List[Tuple[str, float]]        # by host activity, most first

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)


def window(trace: Trace) -> Tuple[float, float]:
    marks = [e for e in trace.host if e.name == WINDOW]
    if not marks:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    return marks[0].start_ns, marks[0].start_ns + marks[0].dur_ns


class _Spans:
    """Annotations of one thread, or of the harness's own threads, that
    follow one another without nesting; for point queries."""

    def __init__(self, events: Sequence[Event]):
        events = sorted(events, key=lambda e: e.start_ns)
        self.start = np.asarray([e.start_ns for e in events], np.float64)
        self.end = self.start + np.asarray([e.dur_ns for e in events],
                                           np.float64)
        self.names = [e.name for e in events]

    def at(self, t: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """For each time, the index of the span that holds it (-1: none)
        and that span's start (-inf: none)."""
        if not self.names:
            return np.full(t.shape, -1), np.full(t.shape, -np.inf)
        i = np.searchsorted(self.start, t, side="right") - 1
        safe = np.clip(i, 0, None)
        hit = (i >= 0) & (self.end[safe] >= t)
        return np.where(hit, i, -1), np.where(hit, self.start[safe], -np.inf)


def _causes(host: Sequence[Event], device: int, mid: np.ndarray,
            length: np.ndarray) -> Dict[str, float]:
    """Idle time by the annotation in flight at each gap's middle: the
    device's own loop thread's, or the harness's where that started later."""
    own = _Spans([e for e in host if e.replica == device])
    shared = _Spans([e for e in host if e.replica is None])
    i_own, s_own = own.at(mid)
    i_shared, s_shared = shared.at(mid)
    names = own.names + shared.names + [OUTSIDE]
    use_own = (i_own >= 0) & (s_own >= s_shared)
    code = np.where(use_own, i_own,
                    np.where(i_shared >= 0, len(own.names) + i_shared,
                             len(names) - 1))
    out: Dict[str, float] = defaultdict(float)
    for name, t in zip(names, np.bincount(code, weights=length,
                                          minlength=len(names))):
        if t:
            out[name] += float(t)
    return out


def summarize(trace: Trace, devices: Sequence[int], top: int = 10) -> Summary:
    """Busy time of each device in ``devices`` over the window, the
    operations that took most device time (control flow that holds other
    operations left out), and idle time by cause in seconds per chip,
    averaged over ``devices``."""
    lo, hi = window(trace)
    host = [e for e in trace.host if e.name != WINDOW]
    op_time: Dict[str, float] = defaultdict(float)
    cause: Dict[str, float] = defaultdict(float)
    busy = {}
    for d in devices:
        start = end = np.zeros(0)
        ops = trace.devices.get(d)
        if ops is not None:
            start = np.clip(ops.start_ns, lo, hi)
            end = np.clip(ops.start_ns + ops.dur_ns, lo, hi)
            inside = end > start
            spent = np.bincount(ops.name_idx[inside],
                                weights=(end - start)[inside],
                                minlength=len(ops.names))
            for name, ns in zip(ops.names, spent):
                if ns and not name.startswith(CONTAINERS):
                    op_time[name] += float(ns) * 1e-9
            start, end = union(start[inside], end[inside])
        busy[d] = float((end - start).sum()) * 1e-9
        gap_lo, gap_hi = idle(start, end, lo, hi)
        for name, t in _causes(host, d, (gap_lo + gap_hi) / 2,
                               (gap_hi - gap_lo) * 1e-9 / len(devices)).items():
            cause[name] += t
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(cause.items(), key=lambda kv: -kv[1])[:top]
    return Summary((hi - lo) * 1e-9, busy, ops_top, gaps_top)
