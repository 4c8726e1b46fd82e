"""The reduction from a profiler trace to busy time, idle share and the
breakdown: on hand-made events, on a short cut of a trace recorded on a
TPU v5e, and through JAX's own reader on a trace recorded here."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import trace
from conftest import ROOT

FIXTURE = (ROOT / "tests" / "chipbench" / "fixtures"
           / "v5e_chat_trace_cut.json.gz")
MS = 1e6


def _ops(*rows):
    """Device operations from (name, start ms, duration ms) rows."""
    names = sorted({r[0] for r in rows})
    return trace.DeviceOps(names,
                           np.asarray([names.index(r[0]) for r in rows]),
                           np.asarray([r[1] * MS for r in rows]),
                           np.asarray([r[2] * MS for r in rows]))


def _host(name, start_ms, dur_ms, replica=None):
    return trace.Event(name, start_ms * MS, dur_ms * MS, replica)


def test_union_and_idle():
    start, end = trace.union(np.array([5., 0, 1, 6]), np.array([7., 2, 3, 9]))
    assert start.tolist() == [0, 5] and end.tolist() == [3, 9]
    lo, hi = trace.idle(start, end, -1, 10)
    assert lo.tolist() == [-1, 3, 9] and hi.tolist() == [0, 5, 10]
    assert trace.op_name("%copy.62 = bf16[1,32]{1,0} copy(%x)") == "copy.62"


def test_summary_by_hand():
    tr = trace.Trace(
        host=[_host(trace.WINDOW, 0, 100),
              # device 0's loop steps 0-45 and exports 90-100; the
              # harness's idle span covers every loop
              _host("engine.step", 0, 45, replica=0),
              _host("move.export", 90, 10, replica=0),
              _host("harness.idle", 0, 100)],
        devices={
            # busy 0-30 and 50-90, under a loop that holds 50-90
            0: _ops(("fusion.1", 0, 30), ("fusion.2", 50, 30),
                    ("fusion.1", 60, 30), ("while.3", 50, 40)),
            # busy 10-20, and an op that runs past the window's end
            1: _ops(("copy", 10, 10), ("fusion.2", 95, 20))})
    s = trace.summarize(tr, [0, 1])
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s[0] == pytest.approx(0.070)
    assert s.busy_s[1] == pytest.approx(0.015)
    assert s.mean_busy_s == pytest.approx(0.0425)
    ops = dict(s.device_ops)
    assert ops["fusion.1"] == pytest.approx(0.060)
    assert ops["fusion.2"] == pytest.approx(0.035)
    assert "while.3" not in ops
    gaps = dict(s.idle_gaps)   # seconds per chip, averaged over both
    # device 0: 30-50 inside its engine.step (and the harness's span, which
    # starts with it), 90-100 in move.export; device 1: 0-10 and 20-95
    assert gaps["engine.step"] == pytest.approx(0.020 / 2)
    assert gaps["move.export"] == pytest.approx(0.010 / 2)
    assert gaps["harness.idle"] == pytest.approx(0.085 / 2)
    assert sum(gaps.values()) == pytest.approx((0.2 - 0.085) / 2)


def test_missing_window_is_an_error():
    with pytest.raises(ValueError, match="chipbench.window"):
        trace.summarize(trace.Trace([], {0: _ops(("fusion", 0, 1))}), [0])


def test_recorded_v5e_trace():
    """300 ms of granite2b.chat's window on a TPU v5e: 3.7 decode steps,
    72,705 operations."""
    tr = trace.load(FIXTURE)
    assert sum(o.name_idx.size for o in tr.devices.values()) == 72705
    s = trace.summarize(tr, [0])
    assert s.window_s == pytest.approx(0.3)
    assert s.busy_s[0] == pytest.approx(0.292404, rel=1e-5)
    assert s.device_ops[0] == ("copy.62", pytest.approx(0.0321606, rel=1e-5))
    assert all(not name.startswith("while") for name, _ in s.device_ops)
    gaps = dict(s.idle_gaps)
    assert gaps["engine.step"] == pytest.approx(0.0072251, rel=1e-4)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s[0])


def test_reads_a_trace_recorded_here(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        with jax.profiler.TraceAnnotation("engine.step", replica=0):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    tr = trace.read_xplane(path)
    steps = [e for e in tr.host if e.name == "engine.step"]
    assert len(steps) == 1 and steps[0].replica == 0
    s = trace.summarize(tr, [0])            # the CPU has no device plane
    assert s.window_s > 0 and s.busy_s[0] == 0
    assert sum(t for _, t in s.idle_gaps) == pytest.approx(s.window_s)
    trace.save(tr, tmp_path / "events.json.gz")
    assert trace.load(tmp_path / "events.json.gz").host == tr.host
