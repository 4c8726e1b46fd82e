"""The traffic generator and the open-loop arithmetic of the latencies."""

import json

import numpy as np
import pytest

from chipbench import generator, readings, serving, spec
from conftest import ROOT
from repro.serve import Request

MIXES = sorted(p.stem for p in (ROOT / "chipbench" / "traffic").glob("*.json"))


def _mix(name):
    return json.loads((ROOT / "chipbench" / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", MIXES)
def test_schedule_is_deterministic_per_seed(name):
    mix = _mix(name)
    a = generator.schedule(mix, 1.1, 50, 2 ** 31 + 5, 49155)
    b = generator.schedule(mix, 1.1, 50, 2 ** 31 + 5, 49155)
    c = generator.schedule(mix, 1.1, 50, 2 ** 31 + 6, 49155)
    assert a == b
    assert a != c


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    """Seeds reorder one fixed set of gaps and lengths per phase."""
    mix = _mix(name)
    runs = [generator.schedule(mix, 1.1, 50, seed, 49155)
            for seed in (1, 2 ** 33 + 1)]
    ends = {"warm": mix["warm_s"], "window": mix["warm_s"] + 50,
            "drain": mix["warm_s"] + 50 + mix["drain_s"]}
    for phase, end in ends.items():
        sets = [[a for a in run if a.phase == phase] for run in runs]
        for key in (lambda a: len(a.prompt), lambda a: a.max_new):
            assert sorted(map(key, sets[0])) == sorted(map(key, sets[1]))
        gaps = [np.diff([a.due for a in s] + [end]) for s in sets]
        np.testing.assert_allclose(np.sort(gaps[0]), np.sort(gaps[1]))


@pytest.mark.parametrize("name", MIXES)
def test_sample_means_match_the_parameters(name):
    mix = _mix(name)
    run = generator.schedule(mix, 2.0, 50, 7, 49155)
    window = [a for a in run if a.phase == "window"]
    assert len(window) == 100                       # rate x seconds
    for key, spec_ in ((lambda a: len(a.prompt), mix["prompt_len"]),
                       (lambda a: a.max_new, mix["output_len"])):
        values = [key(a) for a in window]
        assert min(values) >= spec_["min"] and max(values) <= spec_["max"]
        assert np.mean(values) == pytest.approx(
            generator.length_mean(spec_), rel=0.02)
    # Poisson gaps: mean 1/rate, coefficient of variation near 1.
    gaps = np.diff([a.due for a in window])
    assert np.mean(gaps) == pytest.approx(0.5, rel=0.05)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.15)
    assert all(0 <= t < 49155 for a in window for t in a.prompt)


def test_chat_lengths_are_the_published_means():
    mix = _mix("chat")
    assert mix["prompt_len"]["mean"] == 70 and mix["output_len"]["mean"] == 215
    assert mix["prompt_len"]["sigma"] == mix["output_len"]["sigma"] == 1.0


def _record(tokens_by_req, due_by_req, window=(10.0, 20.0), stop=25.0):
    rec = serving.Recorder()
    for rid, toks in tokens_by_req.items():
        a = generator.Arrival(rid, due_by_req[rid], [1], 3, "window")
        rec.requests[rid] = serving.ReqRecord(a, Request(rid, [1], 3),
                                              due_by_req[rid], tokens=toks)
    win = serving.Window(0.0, window[0], window[1], stop)
    return readings.RunRecord(None, win, window[0], window[1], 1.0, rec, None,
                              None)


def test_unserved_request_enters_the_tail_at_stop_minus_due():
    run = _record({0: [11.0, 11.5], 1: [12.0, 13.0], 2: []},
                  {0: 10.5, 1: 11.0, 2: 19.0})
    assert readings.ttfts(run) == [0.5, 1.0, 6.0]     # 25 - 19 for the last
    # A stall shows: had request 2 been served at 19.1, p90 would be 0.92.
    assert readings.percentile(readings.ttfts(run), 90) == pytest.approx(5.0)


def test_token_gaps_count_those_ending_in_the_window():
    run = _record({0: [9.0, 10.5, 11.0], 1: [19.5, 20.5]},
                  {0: 8.0, 1: 19.0})
    assert sorted(readings.token_gaps(run)) == [0.5, 1.5]


def test_percentile_is_interpolated_and_empty_is_none():
    assert readings.percentile(range(11), 90) == pytest.approx(9.0)
    assert readings.percentile([], 90) is None


def test_move_gap_waits_for_the_first_token_on_the_destination():
    run = _record({0: [10.0, 11.0, 12.5]}, {0: 9.0})
    move = serving.MoveRecord(0, 1, 0, 11.1, 11.4, 11.0, 2, 100, 10)
    assert readings.move_gap(run, move) == pytest.approx(1.5)
    early = serving.MoveRecord(0, 1, 0, 12.6, 12.7, 12.5, 3, 100, 10)
    assert readings.move_gap(run, early) is None


def test_cell_files_name_what_exists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.rate_per_s > 0 and "logit_gap" in cell.limits
        assert cell.chips == int(cell.traffic.get("replicas", 1))
