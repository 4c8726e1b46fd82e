"""Whole runs of a tiny one-replica cell on the CPU, past the harness's look
for a chip: a sound run is correct, and each fault planted in the timed
path underneath, or the float8 control put in the program's place, makes
``correct`` false."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, run, spec
from conftest import TINY_LIMIT, make_root
from repro.serve import engine as serve_engine

SEED = 2 ** 31 + 17
_decode = serve_engine.make_decode_step


def _run(tmp_path, seconds=2.0, control=False, rate=6.0):
    cell = spec.load_cell("tiny.chat", make_root(tmp_path, rate=rate))
    return run.run_cell(cell, SEED, seconds, False, jax.devices()[:1],
                        control=control)


# Faulty runs offer more than the tiny engine serves, so that every slot,
# the second half of the batch too, holds requests all through the run.
OVERLOAD = 40.0


def test_sound_run_is_correct(tmp_path):
    out = _run(tmp_path, control=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_s", "itl_p95_ms", "setup_s"}
    assert out["checks"]["logit_gap"]["value"] <= TINY_LIMIT
    assert out["checks"]["compared_requests"]["value"] >= 2
    # The control, put in the program's place at the same prompts and
    # tokens, fails the limit.
    assert out["readings"]["control_gap"] > TINY_LIMIT
    assert list(out)[-1] == "checks"


def _stale_state(cfg):
    real = _decode(cfg)
    return lambda params, cache, tokens: (cache, real(params, cache, tokens)[1])


def _half_batch(cfg):
    real = _decode(cfg)

    def step(params, cache, tokens):
        cache, logits = real(params, cache, tokens)
        half = logits.shape[0] // 2
        return cache, jnp.concatenate([logits[:half], logits[:half]])
    return step


@pytest.mark.parametrize("fault", [_stale_state, _half_batch],
                         ids=["state-unchanged", "half-batch"])
def test_broken_decode_step_is_not_correct(tmp_path, monkeypatch, fault):
    monkeypatch.setattr(serve_engine, "make_decode_step", fault)
    out = _run(tmp_path, rate=OVERLOAD)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > TINY_LIMIT


def test_altered_token_is_not_correct(tmp_path, monkeypatch):
    real = serve_engine.sample

    def altered(logits, key, temperature=0.0):
        tok = real(logits, key, temperature)
        return tok.at[0].set((tok[0] + 1) % logits.shape[-1])
    monkeypatch.setattr(serve_engine, "sample", altered)
    out = _run(tmp_path, rate=OVERLOAD)
    assert not out["correct"]


def test_sample_holds_the_longest_and_moved_requests():
    class R:
        def __init__(self, i, n, moved):
            self.arrival = type("A", (), {"req_id": i})()
            self.request = type("Q", (), {"output": [0] * n})()
            self.moved = moved
    recs = [R(i, n, m) for i, (n, m) in enumerate(
        [(5, 0), (50, 0), (7, 1), (9, 0), (3, 1), (4, 0), (8, 0)])]
    picked = check.sample(recs, 4, 3, moved_wanted=2)
    assert picked[0].arrival.req_id == 1
    assert {r.arrival.req_id for r in picked[1:3]} == {2, 4}
    assert len(picked) == 4
    assert check.sample(recs, 4, 3, 2) == picked          # seeded
    assert [r.arrival.req_id for r in check.sample(recs, 99, 3, 0)][0] == 1


def test_checks_fail_on_nan_and_on_missing_moves():
    class R:
        moved = 0
        arrival = type("A", (), {"max_new": 2})()
        request = type("Q", (), {"output": [1, 2]})()
    out = check.checks([R()], {"program": [np.array([np.nan])]},
                       {"logit_gap": 0.1}, moves_expected=True)
    assert not out["logit_gap"]["ok"]
    assert not out["moved_compared"]["ok"]
    assert out["short_outputs"]["ok"]
