"""The serving program's own spans and device scopes in a profiler trace.

Additions to ``chipbench.trace``.  The program (``repro.serve.trace``)
writes host spans ``serve.*``, each keyed to its chip by a ``device``
stat, and names the decode program's cache and attention work with
``jax.named_scope``.  ``read_xplane`` reads those spans beside the
harness's annotations into a ``trace.Trace`` (a span's chip goes where a
harness annotation keeps its replica).  The scopes are in the compiled
program's op-name metadata, which a TPU v5e trace's operation events do
not carry; ``scopes_from_hlo`` reads them from the compiled decode
program's text, which names the same instructions, and ``scoped`` gives
each traced operation its scope by name.  ``summarize`` attributes each
idle gap to the innermost span in flight, the latest-starting one that
holds the gap's middle (on spans that do not nest, exactly
``trace.summarize``'s attribution), and adds device time by scope and the
program's steps.

    python3 -m chipbench.program_trace --workload <cell> --seed <n> --seconds <s> [--cut <file>]

runs a cell as ``chipbench.run --trace 1`` does, reduces its trace both
ways, and prints the result line with a ``program`` part: the readings of
the program's spans and scopes.  ``--cut`` also keeps 300 ms of the trace
around an admitting step, in ``save``'s format.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import gzip
import json
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import trace
from repro.serve.trace import SCOPES

PREFIX = "serve."
STEP, ADMIT = "serve.step", "serve.admit"
UNSCOPED = ""
_DEVICE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_HLO_OP = re.compile(
    r'^\s*(?:ROOT )?%?(\S+) = .*metadata=\{[^}]*op_name="([^"]*)"')


@dataclasses.dataclass
class ScopedTrace:
    trace: trace.Trace                # harness annotations and serve.* spans
    scopes: Dict[int, List[str]]      # per device, the scope of each name in
                                      # its ``DeviceOps.names`` (UNSCOPED: none)


def scope_of(op_path: str) -> str:
    """The innermost of ``SCOPES`` in an op-name path
    (``jit(decode)/while/body/serve_kv/dynamic_slice``)."""
    for part in reversed(op_path.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def read_xplane(path) -> trace.Trace:
    """``trace.read_xplane``'s events and the program's ``serve.*`` spans."""
    from jax.profiler import ProfileData     # needs only JAX's own reader
    data = ProfileData.from_file(str(path))
    host, devices = [], {}
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name == trace.OPS_LINE:
                index: Dict[str, int] = {}
                idx, start, dur = [], [], []
                for e in line.events:
                    idx.append(index.setdefault(trace.op_name(e.name),
                                                len(index)))
                    start.append(e.start_ns)
                    dur.append(e.duration_ns)
                devices[int(m.group(1))] = trace.DeviceOps(
                    list(index), np.asarray(idx, np.int32),
                    np.asarray(start, np.float64), np.asarray(dur, np.float64))
            elif not m:
                for e in line.events:
                    if e.name.startswith(trace.HOST_PREFIXES):
                        key = "replica"
                    elif e.name.startswith(PREFIX):
                        key = "device"
                    else:
                        continue
                    chip = dict(e.stats).get(key)
                    host.append(trace.Event(e.name, float(e.start_ns),
                                            float(e.duration_ns),
                                            None if chip is None else int(chip)))
    return trace.Trace(host, devices)


def scopes_from_hlo(text: str) -> Dict[str, str]:
    """Instruction name -> scope, for the scoped instructions of a compiled
    program's text (``compiled.as_text()``)."""
    out = {}
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if m and scope_of(m.group(2)):
            out[m.group(1)] = scope_of(m.group(2))
    return out


def scoped(tr: trace.Trace, by_name: Dict[str, str]) -> ScopedTrace:
    """Each operation given the scope ``by_name`` holds for its name."""
    return ScopedTrace(tr, {d: [by_name.get(name, UNSCOPED)
                                for name in ops.names]
                            for d, ops in tr.devices.items()})


def harness_only(tr: trace.Trace) -> trace.Trace:
    """The events ``trace.read_xplane`` reads, for ``trace.summarize``."""
    return trace.Trace([e for e in tr.host if not e.name.startswith(PREFIX)],
                       tr.devices)


# ------------------------------------------------------------ reduction --
def _innermost(start: np.ndarray, end: np.ndarray,
               t: np.ndarray) -> np.ndarray:
    """For each time, the index of the latest-starting interval that holds
    it (-1: none); ``start`` ascending.  Intervals may nest or overlap."""
    # prev[j]: the last interval before j that ends later than j.  Those
    # between end no later than j, so when j ends before t, so do they.
    if not start.size:
        return np.full(t.shape, -1)
    prev = np.full(start.size, -1)
    stack: List[int] = []
    for j, e in enumerate(end):
        while stack and end[stack[-1]] <= e:
            stack.pop()
        prev[j] = stack[-1] if stack else -1
        stack.append(j)
    i = np.searchsorted(start, t, side="right") - 1
    while True:
        safe = np.clip(i, 0, None)
        miss = (i >= 0) & (end[safe] < t)
        if not miss.any():
            return i
        i = np.where(miss, prev[safe], i)


def _causes(host: Sequence[trace.Event], device: int, mid: np.ndarray,
            length: np.ndarray) -> Dict[str, float]:
    """Idle time by the innermost annotation in flight at each gap's middle,
    of the device's own (its loop thread's, the program's spans for it) and
    the harness's own; on equal starts the device's own, then the shorter."""
    spans = sorted((e for e in host if e.replica in (device, None)),
                   key=lambda e: (e.start_ns, e.replica is not None,
                                  -e.dur_ns))
    start = np.asarray([e.start_ns for e in spans], np.float64)
    end = start + np.asarray([e.dur_ns for e in spans], np.float64)
    names = [e.name for e in spans] + [trace.OUTSIDE]
    code = _innermost(start, end, mid)
    code = np.where(code >= 0, code, len(names) - 1)
    out: Dict[str, float] = defaultdict(float)
    for name, t in zip(names, np.bincount(code, weights=length,
                                          minlength=len(names))):
        if t:
            out[name] += float(t)
    return out


@dataclasses.dataclass(frozen=True)
class Step:
    device: int
    start_ns: float
    dur_ns: float
    admitted: bool                    # held a ``serve.admit``


@dataclasses.dataclass
class ProgramSummary:
    """Seconds are per chip, averaged over the devices summarized."""
    window_s: float
    busy_s: float
    idle_gaps: List[Tuple[str, float]]   # by the innermost span, most first
    serve_idle_s: float                  # idle under ``serve.*`` spans
    scope_s: Dict[str, float]            # device time by scope (UNSCOPED: none)
    unscoped_ops: List[Tuple[str, float]]  # the largest unscoped operations
    steps: List[Step]                    # ``serve.step`` spans in the window


def _steps(host: Sequence[trace.Event], devices: Sequence[int], lo: float,
           hi: float) -> List[Step]:
    out = []
    for d in devices:
        admits = np.sort([e.start_ns for e in host
                          if e.name == ADMIT and e.replica == d])
        for e in host:
            if (e.name == STEP and e.replica == d and e.start_ns >= lo
                    and e.start_ns + e.dur_ns <= hi):
                k = np.searchsorted(admits, e.start_ns)
                held = k < admits.size and admits[k] <= e.start_ns + e.dur_ns
                out.append(Step(d, e.start_ns, e.dur_ns, bool(held)))
    return sorted(out, key=lambda s: s.start_ns)


def summarize(st: ScopedTrace, devices: Sequence[int],
              top: int = 5) -> ProgramSummary:
    """Idle time by the innermost span in flight, device time by scope,
    the ``top`` largest unscoped operations, and the program's steps, over
    the window of ``trace.summarize``."""
    tr = st.trace
    lo, hi = trace.window(tr)
    host = [e for e in tr.host if e.name != trace.WINDOW]
    n = len(devices)
    cause: Dict[str, float] = defaultdict(float)
    scope_s: Dict[str, float] = defaultdict(float)
    unscoped: Dict[str, float] = defaultdict(float)
    busy = 0.0
    for d in devices:
        start = end = np.zeros(0)
        ops = tr.devices.get(d)
        if ops is not None:
            start = np.clip(ops.start_ns, lo, hi)
            end = np.clip(ops.start_ns + ops.dur_ns, lo, hi)
            inside = end > start
            spent = np.bincount(ops.name_idx[inside],
                                weights=(end - start)[inside],
                                minlength=len(ops.names))
            for name, scope, ns in zip(ops.names, st.scopes[d], spent):
                if ns and not name.startswith(trace.CONTAINERS):
                    scope_s[scope] += float(ns) * 1e-9 / n
                    if scope == UNSCOPED:
                        unscoped[name] += float(ns) * 1e-9 / n
            start, end = trace.union(start[inside], end[inside])
        busy += float((end - start).sum()) * 1e-9 / n
        gap_lo, gap_hi = trace.idle(start, end, lo, hi)
        for name, t in _causes(host, d, (gap_lo + gap_hi) / 2,
                               (gap_hi - gap_lo) * 1e-9 / n).items():
            cause[name] += t
    gaps = sorted(cause.items(), key=lambda kv: -kv[1])
    return ProgramSummary(
        (hi - lo) * 1e-9, busy, gaps,
        sum(t for name, t in gaps if name.startswith(PREFIX)), dict(scope_s),
        sorted(unscoped.items(), key=lambda kv: -kv[1])[:top],
        _steps(host, devices, lo, hi))


def readings(s: ProgramSummary, chips: int) -> Dict[str, Optional[float]]:
    """The program's per-layer numbers: device idle under ``serve.*`` spans
    per step; the median admitting step minus the median other step (as
    ``metrics/admit_cost_ms.chat.py``); device time under each scope per
    step (ms); the unscoped share of the scoped-or-not operation time (%)."""
    steps = len(s.steps) / chips
    admit = [x.dur_ns for x in s.steps if x.admitted]
    other = [x.dur_ns for x in s.steps if not x.admitted]
    ops_s = sum(s.scope_s.values())

    def per_step(seconds: float) -> Optional[float]:
        return seconds / steps * 1e3 if steps else None
    return {
        "host_idle_ms": per_step(s.serve_idle_s),
        "admit_cost_ms": (float(np.median(admit) - np.median(other)) * 1e-6
                          if admit and other else None),
        "kv_cache_ms": per_step(s.scope_s.get(SCOPES[0], 0.0)),
        "attention_ms": per_step(s.scope_s.get(SCOPES[1], 0.0)),
        "unscoped_share": (100.0 * s.scope_s.get(UNSCOPED, 0.0) / ops_s
                           if ops_s else None),
    }


def report(s: ProgramSummary, chips: int) -> Dict:
    """``readings`` with what they come from: steps (all, admitting), the
    step time's percentiles 50/90/95/99 in ms for admitting and other
    steps, device seconds by scope, idle under ``serve.*``, the largest
    idle causes and unscoped operations."""
    quantiles = {}
    for kind, admitted in (("admitting", True), ("other", False)):
        ms = [x.dur_ns * 1e-6 for x in s.steps if x.admitted == admitted]
        if ms:
            quantiles[kind] = np.percentile(ms, [50, 90, 95, 99]).tolist()
    return dict(readings(s, chips), steps=len(s.steps),
                admitting_steps=sum(x.admitted for x in s.steps),
                step_ms=quantiles, scope_s=s.scope_s,
                serve_idle_s=s.serve_idle_s,
                idle_gaps=[list(x) for x in s.idle_gaps[:12]],
                unscoped_ops=[list(x) for x in s.unscoped_ops])


# ------------------------------------------------------------- storage --
def save(st: ScopedTrace, path) -> None:
    """``trace.save``'s format, each device with its ``scopes``."""
    tr = st.trace
    body = {"host": [dataclasses.astuple(e) for e in tr.host],
            "devices": {str(d): {"names": ops.names,
                                 "scopes": st.scopes[d],
                                 "name_idx": ops.name_idx.tolist(),
                                 "start_ns": ops.start_ns.tolist(),
                                 "dur_ns": ops.dur_ns.tolist()}
                        for d, ops in tr.devices.items()}}
    with gzip.open(path, "wt") as f:
        json.dump(body, f)


def load(path) -> ScopedTrace:
    with gzip.open(path, "rt") as f:
        body = json.load(f)
    return ScopedTrace(trace.load(path),
                       {int(d): o["scopes"] for d, o in body["devices"].items()})


def cut(st: ScopedTrace, lo: float, hi: float) -> ScopedTrace:
    """The events that overlap [lo, hi] ns, under a window of just that."""
    host = [e for e in st.trace.host if e.name != trace.WINDOW
            and e.start_ns < hi and e.start_ns + e.dur_ns > lo]
    devices = {}
    for d, ops in st.trace.devices.items():
        keep = (ops.start_ns < hi) & (ops.start_ns + ops.dur_ns > lo)
        devices[d] = trace.DeviceOps(ops.names, ops.name_idx[keep],
                                     ops.start_ns[keep], ops.dur_ns[keep])
    return ScopedTrace(trace.Trace([trace.Event(trace.WINDOW, lo, hi - lo)]
                                   + host, devices), st.scopes)


# ---------------------------------------------------------- entry point --
def decode_hlo(cell, device) -> str:
    """The compiled text of the cell's decode program, as the engine
    builds it."""
    import jax
    import jax.numpy as jnp

    from repro.models import init_cache, init_lm
    from repro.serve.engine import make_decode_step

    cfg = cell.arch.model_config(cell.config)
    e = cell.config["engine"]
    one = jax.sharding.SingleDeviceSharding(device)

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)
    params = shaped(jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg)))
    cache = shaped(jax.eval_shape(lambda: init_cache(
        cfg, int(e["slots"]), int(e["max_len"]), per_slot_index=True)))
    tokens = jax.ShapeDtypeStruct((int(e["slots"]), 1), jnp.int32,
                                  sharding=one)
    return jax.jit(make_decode_step(cfg), donate_argnums=(1,)).lower(
        params, cache, tokens).compile().as_text()


def _around_an_admit(st: ScopedTrace, ms: float) -> Tuple[float, float]:
    """[lo, hi] ns: ``ms`` of the window from 100 ms before the step of the
    first admission 1 s or more into it."""
    lo, hi = trace.window(st.trace)
    admits = sorted(e.start_ns for e in st.trace.host
                    if e.name == ADMIT and e.start_ns >= lo + 1e9)
    steps = [e.start_ns for e in st.trace.host if e.name == STEP
             and admits and e.start_ns <= admits[0]]
    at = max(steps) - 1e8 if steps else lo
    return at, min(at + ms * 1e6, hi)


def traced_run(cell, seed: int, seconds: float,
               devices: Sequence) -> Tuple[Dict, trace.Trace]:
    """``run.run_cell`` with the profiler on, and the trace it reduced,
    read once for both reductions."""
    from chipbench import run

    held: Dict[str, trace.Trace] = {}

    def reduce(devs) -> trace.Summary:
        files = sorted(glob.glob(str(run.TRACE_DIR / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{run.TRACE_DIR}")
        tr = held["trace"] = read_xplane(files[-1])
        return trace.summarize(harness_only(tr), [d.id for d in devs])

    harness_reduce, run._trace_summary = run._trace_summary, reduce
    try:
        result = run.run_cell(cell, seed, seconds, True, devices)
    finally:
        run._trace_summary = harness_reduce
    return result, held["trace"]


def main(argv=None) -> int:
    from chipbench import run, spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cut", default=None,
                    help="keep 300 ms of the trace here (gzipped JSON)")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    devices = run.chip_devices(cell.chips)
    run.configure_compile_cache()
    result, tr = traced_run(cell, args.seed, args.seconds, devices)
    st = scoped(tr, scopes_from_hlo(decode_hlo(cell, devices[0])))
    ids = [d.id for d in devices]
    s = summarize(st, ids)
    if args.cut:
        save(cut(st, *_around_an_admit(st, 300.0)), args.cut)
    result["program"] = report(s, len(ids))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
