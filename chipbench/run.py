"""Run one benchmark cell on the chip and print its result line.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  Steps, in order: the device must be a TPU
with the cell's chips (else exit non-zero, no result); weights from the
seed on the device; engines built and every program they run compiled
(from the compile cache in ``.jax_cache/``) and run once; the cell's
traffic for ``warm_s``, then the measured window of ``--seconds``, then
until every request due in the window has its first token (``drain_s`` at
most); then, with the engines freed, the comparison with the plain
reference.  With ``--trace 1`` the window is traced by the profiler and the
per-layer metrics are reported instead of the end-to-end ones.

Logs and the compared numbers go to stderr, the compared numbers last; the
last line of stdout is the JSON result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Sequence  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import check, generator, program, serving, trace, weights  # noqa: E402
from chipbench.peaks import PEAKS, peaks  # noqa: E402
from chipbench.readings import RunRecord, window_requests  # noqa: E402
from chipbench.spec import Cell, load_cell, metric_reader  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench" / "trace"
PROFILER_LEAD_S = 3.0      # the profiler's start-up stall fits in this


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def chip_devices(chips: int) -> List:
    """The first ``chips`` TPU devices; raises off a TPU, with too few
    chips, or on a chip with no published peaks."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks(devs[0].device_kind)
    return devs[:chips]


def configure_compile_cache() -> None:
    """JAX's persistent cache at one fixed path in the checkout, keeping
    every program, so that only a checkout's first run compiles."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts backend compiles and compile-cache reads while ``armed``."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        self.armed = False
        self.count = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._on_event)
        return cls._instance

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.armed and ("backend_compile" in event
                           or "cache_retrieval" in event):
            self.count += 1


def _memory_peak(devices) -> int:
    peaks_seen = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices]
    return int(max(peaks_seen))


def _trace_summary(devices) -> trace.Summary:
    files = sorted(glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {TRACE_DIR}")
    return trace.summarize(trace.read_xplane(files[-1]),
                           [d.id for d in devices])


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             devices: Sequence, control: bool = False) -> Dict:
    """One run of ``cell`` on ``devices``; the result as a dict."""
    mix, config, arch = cell.traffic, cell.config, cell.arch
    model = config["model"]
    replicas_n = int(mix.get("replicas", 1))
    cfg = arch.model_config(config)

    t = time.perf_counter()
    w = weights.generate(arch, model, seed, devices[0])
    params = program.params(arch, w, cfg)
    jax.block_until_ready(params)
    log(f"weights: {sum(x.size for x in w.values())} parameters on "
        f"{devices[0]} in {time.perf_counter() - t:.3f} s")
    rec = serving.Recorder()
    note = serving.Annotator(traced)
    replicas = [serving.Replica(i, program.engine(config, cfg, params,
                                                  devices[i % len(devices)]),
                                rec, note)
                for i in range(replicas_n)]
    del w, params
    t = time.perf_counter()
    for rep in replicas:
        serving.warm_up(rep)
    log(f"warm-up: {replicas_n} replica(s) in {time.perf_counter() - t:.3f} s")

    arrivals = generator.schedule(mix, cell.rate_per_s, seconds, seed,
                                  model["vocab_size"])
    compiles = CompileCounter.get()
    warm = float(mix["warm_s"])
    edges = [("start", warm), ("end", warm + seconds)]
    if traced:
        # The profiler starts in the warm-up traffic, where its start-up
        # stall delays no request of the window, and traces ``trace_s`` of
        # the window.
        traced_s = min(float(mix.get("trace_s", seconds)), seconds)
        edges += [("profile", max(0.0, warm - PROFILER_LEAD_S)),
                  ("traced", warm + traced_s)]
    at: Dict[str, float] = {}
    window_note = []          # made once the profiler runs, or it records nothing

    def on_edge(label: str) -> None:
        at[label] = time.perf_counter()
        if label == "profile":
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
        elif label == "start":
            compiles.count, compiles.armed = 0, True
            if traced:
                window_note.append(jax.profiler.TraceAnnotation(trace.WINDOW))
                window_note[0].__enter__()
        elif label == "traced":
            window_note[0].__exit__(None, None, None)
            jax.profiler.stop_trace()
        elif label == "end":
            compiles.armed = False

    runner = serving.Runner(replicas, arrivals, mix, seconds, rec, edges,
                            on_edge)
    win = runner.run()
    memory_peak = _memory_peak(devices)
    summary = _trace_summary(devices) if traced else None
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    chip_kind = devices[0].device_kind
    run = RunRecord(cell, win, at["start"], at["traced" if traced else "end"],
                    at["start"] - PROCESS_START, rec, summary,
                    PEAKS.get(chip_kind))
    lateness = [r.submit - r.due for r in rec.requests.values()]
    due = window_requests(run)
    unserved = sum(1 for r in due if not r.tokens)
    log(f"traffic: {len(rec.requests)} submitted, {len(due)} due in the "
        f"window at {cell.rate_per_s} req/s, {unserved} without a first "
        f"token at the stop; generator late by mean "
        f"{np.mean(lateness) * 1e3:.3f} ms, max {max(lateness) * 1e3:.3f} ms")
    log(f"steps: {len(rec.steps)} in the run; compiles in the window: "
        f"{compiles.count}; moves: {len(rec.moves)} done, "
        f"{rec.skipped_moves} skipped; run stopped "
        f"{win.stop - win.end:.3f} s after the window")
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = metric_reader(cell.root, m.name).read(run)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}
    log(f"{kind} metrics: {json.dumps(metrics)}")

    # The reference runs with the program's state gone.
    finished = [r for r in rec.requests.values() if r.request.done]
    chosen = check.sample(finished, cell.sample, seed,
                          2 if mix.get("move_every_s") else 0)
    del replicas, runner, rep
    gc.collect()
    t = time.perf_counter()
    w = weights.generate(arch, model, seed, devices[0])
    gaps = check.served_gaps(w, model, cell.reference, chosen,
                             int(config["engine"]["max_len"]), control)
    del w
    served = sum(g.size for g in gaps["program"])
    log(f"reference: {len(chosen)} requests, {served} served tokens compared "
        f"in {time.perf_counter() - t:.3f} s")
    results = check.checks(chosen, gaps, cell.limits,
                           bool(mix.get("move_every_s")))
    readings = {}
    if control:
        readings["control_gap"] = max(float(g.max()) for g in gaps["control"]
                                      if g.size)
        log(f"control: widest gap of the float8 pass's choices "
            f"{readings['control_gap']}")

    out = {
        "correct": all(c["ok"] for c in results.values()),
        "attempted": len(due),
        "failed": unserved,
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": chip_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": memory_peak},
    }
    if summary is not None:
        out["device"]["busy_s"] = summary.mean_busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in summary.device_ops],
                            "idle_gaps": [list(x) for x in summary.idle_gaps]}
    if readings:
        out["readings"] = readings
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"],
                         "rule": c["rule"]} for k, c in results.items()}
    for name, c in results.items():
        log(f"check {name}: {c['value']} {c['rule']} {c['limit']} "
            f"{'ok' if c['ok'] else 'FAILED'}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the float8 control's gap (calibration)")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    devices = chip_devices(cell.chips)
    if int(cell.traffic.get("replicas", 1)) > cell.chips:
        raise ValueError(f"{cell.name}: more replicas than chips")
    configure_compile_cache()
    log(f"device: {devices[0].platform} {devices[0].device_kind} x"
        f"{len(jax.devices())}; cell {cell.name}; seed {args.seed}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, bool(args.control))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
