"""The serving program's own spans and scopes, and their reduction: a tiny
``ServeEngine`` under the profiler on the CPU, hand-made nested spans and
scoped operations, a short cut of a trace recorded on a TPU v5e with the
program's spans and scopes, and a traced tiny cell with the compiled
decode program's scopes."""

import glob
import json
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from chipbench import program_trace as pt
from chipbench import run, spec, trace
from chipbench.serving import StepRecord
from conftest import ROOT, make_root
from repro.configs import get_config
from repro.models import init_lm
from repro.models.config import reduced
from repro.serve import Request, ServeEngine
from repro.serve.trace import SCOPES, SPANS

FIXTURES = ROOT / "tests" / "chipbench" / "fixtures"
HARNESS_CUT = FIXTURES / "v5e_chat_trace_cut.json.gz"
SPANS_CUT = FIXTURES / "v5e_chat_trace_spans_cut.json.gz"
MS = 1e6
PHASES = ["serve.feed", "serve.launch", "serve.wait", "serve.emit"]
KV, ATTN = SCOPES


def _engine():
    cfg = reduced(get_config("qwen1.5-0.5b"), vocab_size=64)
    return ServeEngine(cfg, init_lm(jax.random.PRNGKey(0), cfg),
                       batch_slots=2, max_len=48, eos_id=-1)


def _serve(eng):
    """Five requests through two slots, so that admissions fall on several
    steps; the steps that admit, in order."""
    for i in range(5):
        eng.submit(Request(i, prompt=list(range(1, 3 + i)),
                           max_new_tokens=3 + i))
    admitting = []
    while eng.queue or any(s is not None for s in eng.slots):
        admitting.append(bool(eng.queue)
                         and any(s is None for s in eng.slots))
        eng.step()
    return admitting


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """The tiny engine served once with the profiler off and once, warm,
    with it on; the second run's trace as read back."""
    off = _engine()
    _serve(off)
    on = _engine()
    _serve(on)                                  # compiles every program
    on.finished.clear()
    path = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        admitting = _serve(on)
    jax.profiler.stop_trace()
    xplane = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)[0]
    return off, on, admitting, pt.read_xplane(xplane)


def test_every_step_holds_its_phases_in_order(profiled):
    _, eng, admitting, tr = profiled
    spans = sorted((e for e in tr.host if e.name.startswith(pt.PREFIX)),
                   key=lambda e: e.start_ns)
    assert {e.name for e in spans} <= set(SPANS)
    assert {e.replica for e in spans} == {eng.device.id}
    steps = [e for e in spans if e.name == pt.STEP]
    assert len(steps) == len(admitting)
    for step, admits in zip(steps, admitting):
        end = step.start_ns + step.dur_ns
        inner = [e for e in spans if e is not step
                 and step.start_ns <= e.start_ns <= end]
        assert all(e.start_ns + e.dur_ns <= end for e in inner)
        names = [e.name for e in inner]
        assert names == ([pt.ADMIT] if admits else []) + PHASES
        for a, b in zip(inner, inner[1:]):
            assert a.start_ns + a.dur_ns <= b.start_ns


def test_requests_carry_their_stamps(profiled):
    _, eng, _, _ = profiled
    assert len(eng.finished) == 5
    for r in eng.finished:
        assert r.t_admit is not None and r.t_first is not None
        assert r.t_admit <= r.t_first


def test_tokens_are_the_same_with_the_profiler_on_and_off(profiled):
    off, on, _, _ = profiled
    outputs = lambda eng: {r.req_id: r.output for r in eng.finished}
    assert outputs(on) == outputs(off)


def test_steps_and_admissions_read_from_the_trace(profiled):
    _, eng, admitting, tr = profiled
    s = pt.summarize(pt.scoped(tr, {}), [eng.device.id])
    assert [x.admitted for x in s.steps] == admitting
    assert sum(t for _, t in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s)
    r = pt.readings(s, 1)
    assert r["admit_cost_ms"] is not None and r["host_idle_ms"] > 0


def test_scope_of_an_op_name_path():
    assert pt.scope_of("jit(decode)/while/body/closed_call/checkpoint/"
                       "serve_kv/dynamic_slice") == KV
    assert pt.scope_of(f"jit(decode)/{KV}/{ATTN}/dot_general") == ATTN
    assert pt.scope_of("jit(decode)/while/body/dot_general") == pt.UNSCOPED
    hlo = ('  %copy.62 = bf16[1,32]{1,0} copy(%x), metadata={op_name='
           f'"jit(decode)/while/body/{KV}/dynamic_slice" stack_frame_id=12}}\n'
           '  ROOT %fusion.3 = f32[2]{0} fusion(%y), kind=kLoop, metadata='
           '{op_name="jit(decode)/add"}\n')
    assert pt.scopes_from_hlo(hlo) == {"copy.62": KV}
    # the model's kernel scopes, which launch/hlo_stats.py counts, are apart
    assert not any(s.startswith("kscope_") for s in SCOPES)


def _ops(*rows):
    """Device operations from (name, start ms, duration ms) rows."""
    names = sorted({r[0] for r in rows})
    return trace.DeviceOps(names,
                           np.asarray([names.index(r[0]) for r in rows]),
                           np.asarray([r[1] * MS for r in rows]),
                           np.asarray([r[2] * MS for r in rows]))


def _host(name, start_ms, dur_ms, chip=None):
    return trace.Event(name, start_ms * MS, dur_ms * MS, chip)


def test_idle_goes_to_the_innermost_span():
    host = [_host(trace.WINDOW, 0, 100),
            _host("engine.step", 0, 50, 0),        # the harness's, around
            _host("serve.step", 1, 48, 0),         # the program's step
            _host("serve.feed", 2, 3, 0),
            _host("serve.launch", 5, 2, 0),
            _host("serve.wait", 7, 38, 0),
            _host("serve.emit", 45, 3, 0),
            _host("harness.arrivals", 50, 10),
            _host("engine.step", 60, 40, 0),
            _host("serve.step", 61, 38, 0),
            _host("serve.admit", 62, 8, 0)]
    # idle 3-4 (feed), 20-22 (wait), 48.2-48.8 (the step, after emit),
    # 49.2-49.8 (engine.step, after the step), 52-58 (arrivals), 63-65
    # (admit); a loop holds 4-20
    busy = [(0, 3), (4, 20), (22, 48.2), (48.8, 49.2), (49.8, 52), (58, 63),
            (65, 100)]
    ops = _ops(*[("fusion.1", a, b - a) for a, b in busy], ("while.2", 4, 16))
    st = pt.scoped(trace.Trace(host, {0: ops}), {})
    s = pt.summarize(st, [0])
    assert dict(s.idle_gaps) == {
        "serve.feed": pytest.approx(0.001), "serve.wait": pytest.approx(0.002),
        "serve.step": pytest.approx(0.0006),
        "engine.step": pytest.approx(0.0006),
        "harness.arrivals": pytest.approx(0.006),
        "serve.admit": pytest.approx(0.002)}
    assert sum(t for _, t in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s)
    assert s.serve_idle_s == pytest.approx(0.0056)
    assert [(x.admitted, x.dur_ns / MS) for x in s.steps] == [(False, 48),
                                                              (True, 38)]
    r = pt.readings(s, 1)
    assert r["host_idle_ms"] == pytest.approx(2.8)
    assert r["admit_cost_ms"] == pytest.approx(-10.0)


def test_spans_of_another_chip_are_not_its_cause():
    host = [_host(trace.WINDOW, 0, 10), _host("serve.step", 0, 10, 1),
            _host("harness.idle", 0, 10)]
    st = pt.scoped(trace.Trace(host, {0: _ops(("copy", 4, 2))}), {})
    assert dict(pt.summarize(st, [0]).idle_gaps) == {
        "harness.idle": pytest.approx(0.008)}


def test_device_time_by_scope():
    ops = trace.DeviceOps(
        ["copy.62", "fusion.154", "fusion.9", "while.1"],
        np.asarray([0, 1, 2, 3, 0, 2]),
        np.asarray([0, 20, 50, 0, 60, 80]) * MS,
        np.asarray([20, 30, 10, 100, 10, 30]) * MS)
    tr = trace.Trace([_host(trace.WINDOW, 0, 100)], {0: ops})
    # the loop holds the others and counts for no scope; the last
    # operation runs past the window's end
    st = pt.scoped(tr, {"copy.62": KV, "fusion.154": ATTN, "while.1": KV})
    assert st.scopes == {0: [KV, ATTN, "", KV]}
    s = pt.summarize(st, [0])
    assert s.scope_s == {KV: pytest.approx(0.030), ATTN: pytest.approx(0.030),
                         "": pytest.approx(0.030)}
    assert s.unscoped_ops == [("fusion.9", pytest.approx(0.030))]
    assert pt.readings(s, 1)["unscoped_share"] == pytest.approx(100 / 3)


@pytest.mark.parametrize("case", ["by_hand", "v5e_cut"])
def test_on_spans_that_do_not_nest_the_attribution_is_the_harness_s(case):
    """Without nested spans the innermost span is the one
    ``trace.summarize`` names."""
    if case == "v5e_cut":
        tr, devices = trace.load(HARNESS_CUT), [0]
    else:
        tr = trace.Trace(
            host=[_host(trace.WINDOW, 0, 100),
                  _host("engine.step", 0, 45, 0),
                  _host("move.export", 90, 10, 0),
                  _host("harness.idle", 0, 100)],
            devices={0: _ops(("fusion.1", 0, 30), ("fusion.2", 50, 30),
                             ("fusion.1", 60, 30), ("while.3", 50, 40)),
                     1: _ops(("copy", 10, 10), ("fusion.2", 95, 20))})
        devices = [0, 1]
    old = trace.summarize(tr, devices)
    st = pt.scoped(tr, {})
    new = pt.summarize(st, devices)
    assert new.idle_gaps == pytest.approx(old.idle_gaps, rel=1e-12)
    assert [n for n, _ in new.idle_gaps] == [n for n, _ in old.idle_gaps]
    assert new.busy_s == pytest.approx(old.mean_busy_s)


def test_save_cut_and_load(tmp_path):
    host = [_host(trace.WINDOW, 0, 100), _host("serve.step", 10, 20, 0),
            _host("serve.step", 80, 20, 0)]
    st = pt.ScopedTrace(trace.Trace(host, {0: _ops(("copy", 5, 10),
                                                   ("fusion", 50, 10))}),
                        {0: [KV, ""]})
    part = pt.cut(st, 0, 40 * MS)
    assert trace.window(part.trace) == (0, 40 * MS)
    assert [e.name for e in part.trace.host] == [trace.WINDOW, "serve.step"]
    assert part.trace.devices[0].name_idx.tolist() == [0]
    pt.save(part, tmp_path / "cut.json.gz")
    back = pt.load(tmp_path / "cut.json.gz")
    assert back.scopes == {0: [KV, ""]}
    assert back.trace.host == part.trace.host
    # the harness's own reader takes the same file
    assert trace.load(tmp_path / "cut.json.gz").host == part.trace.host


def test_recorded_v5e_trace_with_spans_and_scopes():
    """300 ms of granite2b.chat's window on a TPU v5e, with the program's
    spans and scopes, around a step that admits a request: 61,496
    operations, and a host stall of 48 ms inside one step's wait."""
    st = pt.load(SPANS_CUT)
    assert sum(o.name_idx.size for o in st.trace.devices.values()) == 61496
    s = pt.summarize(st, [0])
    old = trace.summarize(pt.harness_only(st.trace), [0])
    assert s.window_s == pytest.approx(0.3)
    assert s.busy_s == pytest.approx(old.mean_busy_s)
    assert s.busy_s == pytest.approx(0.2474637, rel=1e-6)
    assert s.scope_s == {KV: pytest.approx(0.1527636, rel=1e-6),
                         ATTN: pytest.approx(0.0477248, rel=1e-6),
                         "": pytest.approx(0.0467654, rel=1e-6)}
    assert s.unscoped_ops[0] == ("bitcast_add_fusion.3",
                                 pytest.approx(0.0119059, rel=1e-5))
    # every idle gap falls under the program's spans, and together they
    # hold what the harness's reading puts under engine.step
    assert s.idle_gaps == [("serve.wait", pytest.approx(0.0525363, rel=1e-6))]
    assert s.serve_idle_s == pytest.approx(dict(old.idle_gaps)["engine.step"])
    assert sum(t for _, t in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s)
    assert [(x.admitted, round(x.dur_ns * 1e-6, 3)) for x in s.steps] == [
        (True, 77.686), (False, 77.474)]


def test_a_traced_tiny_cell_reports_the_program_s_readings(tmp_path,
                                                          monkeypatch):
    """A whole traced run of the tiny cell on the CPU: the two new
    host-clock metrics are read, and the program's spans reach the
    reduction (the CPU has no device plane, so no scope)."""
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    root = make_root(tmp_path, rate=8.0)
    cell = spec.load_cell("tiny.chat", root)
    out, tr = pt.traced_run(cell, 2 ** 31 + 41, 2.0, jax.devices()[:1])
    assert out["correct"], out["checks"]
    assert out["metrics"]["prefill_s.chat"]["value"] > 0
    assert "admit_cost_ms.chat" in out["metrics"]
    # the compiled decode program names both scopes
    names = pt.scopes_from_hlo(pt.decode_hlo(cell, jax.devices()[0]))
    assert set(names.values()) == set(SCOPES)
    s = pt.summarize(pt.scoped(tr, names), [jax.devices()[0].id])
    assert s.steps and any(x.admitted for x in s.steps)
    printed = json.loads(json.dumps(pt.report(s, 1)))
    assert printed["steps"] == len(s.steps)
    assert set(printed["step_ms"]) == {"admitting", "other"}
    # the harness's own breakdown reads none of them
    assert not any(name.startswith(pt.PREFIX)
                   for name, _ in out["breakdown"]["idle_gaps"])


def _stamped(admit, first):
    return SimpleNamespace(request=SimpleNamespace(t_admit=admit,
                                                   t_first=first))


def test_prefill_and_admission_readers_by_hand():
    """Steps of 0.9 s from 0 to 30 s, 0.95 s where a request is admitted
    and 0.99 s once; the profiler's stop at 29.95 s stalls the host until
    50 s."""
    steps = [StepRecord(0, i, i + {1: 0.95, 28: 0.95, 10: 0.99}.get(i, 0.9),
                        ()) for i in range(30)]
    steps += [StepRecord(0, 50 + j, 50.9 + j, ()) for j in range(50)]
    requests = {0: _stamped(1.0, 5.0),
                1: _stamped(28.0, 55.0),     # across the stall of 20.1 s
                2: _stamped(60.0, 62.0),
                3: _stamped(101.0, 103.0),   # admitted after the window
                4: _stamped(99.0, None)}     # no first token at the stop
    rec = SimpleNamespace(steps=steps, requests=requests)
    r = SimpleNamespace(window=SimpleNamespace(start=0.0, end=100.0),
                        opened=0.0, closed=29.95, rec=rec)
    read = lambda name: spec.metric_reader(ROOT, name).read(r)
    assert read("prefill_s.chat") == pytest.approx((4.0 + 6.9 + 2.0) / 3)
    assert read("admit_cost_ms.chat") == pytest.approx(50.0)
    # a program without the stamps gives neither
    rec.requests = {0: SimpleNamespace(request=SimpleNamespace())}
    assert read("prefill_s.chat") is None
    assert read("admit_cost_ms.chat") is None
