"""The readers of a cell whose replicas run one per chip count every chip:
on a record of four replicas, each on its own chip, they read what one
replica alone reads on one chip, where the one-chip ``.chat`` readers read
four times off.  A tiny four-replica cell on four virtual devices reports
every metric of the move cell."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from chipbench import serving, spec, trace
from chipbench.peaks import PEAKS
from chipbench.readings import RunRecord
from conftest import ROOT

CELL = "qwen05b.move4"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ("step_wall_ms.move", "device_ms_per_step.move", "decode_roofline.move")
BUSY_S = 1.5


def _record(replicas):
    """Two seconds traced: each replica runs the same 100 steps of 15 ms
    on 32 slots of growing contexts, and each chip is busy ``BUSY_S``."""
    rec = serving.Recorder()
    for r in range(replicas):
        for i in range(100):
            t0 = 10.0 + 0.02 * i
            rec.steps.append(serving.StepRecord(
                r, t0, t0 + 0.015, tuple(100 + i + s for s in range(32))))
    summary = trace.Summary(2.0, {r: BUSY_S for r in range(replicas)}, [], [])
    return RunRecord(spec.load_cell(CELL), serving.Window(0.0, 10.0, 60.0),
                     10.0, 12.0, 30.0, rec, summary, PEAKS["TPU v5 lite"])


def _read(name, run):
    return spec.metric_reader(ROOT, name).read(run)


def test_the_move_cell_lists_the_readers_that_count_its_chips():
    cell = spec.load_cell(CELL)
    assert cell.chips == 4 and cell.traffic["replicas"] == 4
    names = {m.name for m in cell.per_layer}
    assert set(NEW) <= names
    assert not any(n.endswith(".chat") for n in names)


@pytest.mark.parametrize("name", ["device_ms_per_step", "decode_roofline"])
def test_four_chips_read_what_one_replica_on_one_chip_reads(name):
    four, one = _record(4), _record(1)
    alone = _read(f"{name}.chat", one)
    assert alone > 0
    assert _read(f"{name}.move", one) == pytest.approx(alone)
    assert _read(f"{name}.move", four) == pytest.approx(alone)
    # the one-chip reader on the same four-chip record: four times off
    ratio = _read(f"{name}.chat", four) / alone
    assert ratio == pytest.approx(0.25 if name == "device_ms_per_step" else 4)


def test_step_wall_is_the_mean_over_every_replica_s_steps():
    four = _record(4)
    four.rec.steps[:100] = [serving.StepRecord(0, s.t0, s.t0 + 0.019,
                                               s.contexts)
                            for s in four.rec.steps[:100]]
    assert _read("step_wall_ms.move", four) == pytest.approx(16.0)


def test_no_trace_no_chip_peaks_or_no_steps_read_nothing():
    run = _record(4)
    run.trace = None
    assert all(_read(n, run) is None for n in NEW[1:])
    run = _record(4)
    run.peak = None
    assert _read("decode_roofline.move", run) is None
    run = _record(4)
    run.rec.steps.clear()
    assert all(_read(n, run) is None for n in NEW)


_FOUR = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path[:0] = [sys.argv[2], sys.argv[2] + "/src", sys.argv[2] + "/tests/chipbench"]
    import jax
    import numpy as np
    from conftest import make_root
    from chipbench import peaks, run, spec, trace
    assert len(jax.devices()) == 4
    # The CPU has no peaks and its trace no device planes: give it the
    # v5e's peaks, and each device an operation over each step its
    # replica's loop ran (the CPU computes a step inside the call).
    peaks.PEAKS[jax.devices()[0].device_kind] = peaks.PEAKS["TPU v5 lite"]
    read_xplane = trace.read_xplane

    def with_steps_as_device_work(path):
        tr = read_xplane(path)
        for d in range(4):
            steps = [e for e in tr.host
                     if e.name == "engine.step" and e.replica == d]
            tr.devices[d] = trace.DeviceOps(
                ["step"], np.zeros(len(steps), np.int32),
                np.asarray([e.start_ns for e in steps], np.float64),
                np.asarray([e.dur_ns for e in steps], np.float64))
        return tr

    trace.read_xplane = with_steps_as_device_work
    run.TRACE_DIR = Path(sys.argv[1]) / "trace"     # apart from other runs'
    root = make_root(Path(sys.argv[1]), "tiny.move", 4, rate=12.0)
    out = run.run_cell(spec.load_cell("tiny.move", root), 2 ** 31 + 31, 3.0,
                       True, jax.devices())
    print(json.dumps(out))
""")


def test_a_tiny_four_replica_cell_reads_every_move_metric(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _FOUR, str(tmp_path),
                           str(ROOT)], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    wanted = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", [])}
    assert set(NEW) <= wanted
    values = {n: out["metrics"][n]["value"] for n in wanted}
    assert all(v > 0 for v in values.values()), values
    assert values["decode_roofline.move"] <= 100.0
    assert out["device"]["busy_s"] > 0
