"""Slots that hold no request.  The engine feeds them ``NO_TOKEN``: their
write offset stays where it is and decode attention reads none of their
positions, while the requests beside them decode as they would alone,
through finishing, admission mid-run and a move between engines.
``serve.launch`` counts the slots fed and the positions attention reads."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.kernels import decode_attention as _decode
from repro.kernels import ops
from repro.models import init_lm, reduced
from repro.serve import Request, ServeEngine
from repro.serve.engine import FEED_TOKEN, NO_TOKEN

# GQA 4:1 with heads of 64, as granite-3-2b.
CFG = reduced(get_config("granite-3-2b"), n_heads=8, n_kv_heads=2,
              d_head=64, vocab_size=64)
SPECS = [([5, 6, 7, 8], 10), ([9, 10], 3), ([3, 2, 1], 12), ([4, 4], 6),
         ([1, 2, 3, 4, 5], 7)]


@pytest.fixture(scope="module")
def params():
    return init_lm(jax.random.PRNGKey(0), CFG)


def _engine(params, cfg=CFG, slots=3, max_len=48):
    return ServeEngine(cfg, params, batch_slots=slots, max_len=max_len,
                       eos_id=-1)


def _request(i):
    prompt, new = SPECS[i]
    return Request(i, list(prompt), max_new_tokens=new)


def _lengths_read(monkeypatch):
    """Per layer call, the lengths decode attention is given."""
    seen, real = [], ops.stacked_decode_attention

    def spy(q, k, v, layer, kv_len):
        jax.debug.callback(lambda n: seen.append(np.asarray(n)), kv_len)
        return real(q, k, v, layer, kv_len)
    monkeypatch.setattr(ops, "stacked_decode_attention", spy)
    return seen


def test_a_free_slot_keeps_its_offset_and_reads_nothing(params, monkeypatch):
    seen = _lengths_read(monkeypatch)
    eng = _engine(params)
    eng.submit(_request(1))                      # 2 + 3 tokens: finishes
    eng.submit(_request(2))                      # 3 + 12 tokens
    eng.step()
    while eng.slots[0] is not None:
        eng.step()
    index = np.asarray(eng.cache["index"])
    assert index[0] == 4 and index[2] == 0       # fed 4 tokens; never used
    for _ in range(3):
        jax.block_until_ready(eng.cache)         # the step in flight ran
        del seen[:]
        eng.step()                               # launches the next one
        jax.block_until_ready(eng.cache)
        assert len(seen) == CFG.n_layers
        for lens in seen:
            np.testing.assert_array_equal(lens, [0, eng.offsets[1], 0])
    np.testing.assert_array_equal(np.asarray(eng.cache["index"]),
                                  [4, eng.offsets[1], 0])
    assert (eng._flight.feed[FEED_TOKEN, [0, 2]] == NO_TOKEN).all()


def _alone(params, i):
    eng = _engine(params)
    req = _request(i)
    eng.submit(req)
    eng.run_until_done()
    return req.output


@pytest.mark.parametrize("attention", ["oracle", "kernel"])
def test_requests_decode_as_alone_through_finish_admit_and_move(
        params, monkeypatch, attention):
    """Two engines of three slots: requests finish and free slots, a
    queued one is admitted mid-run, and one session moves between the
    engines (its source slot freed as the harness frees it).  Every
    request's greedy tokens are those it gets alone."""
    want = {i: _alone(params, i) for i in range(len(SPECS))}
    if attention == "kernel":
        monkeypatch.setattr(ops, "stacked_decode_attention",
                            lambda q, k, v, layer, n:
                            _decode.stacked_decode_attention(
                                q, k, v, layer, n, block_bytes=1 << 13,
                                interpret=True))
    src, dst = _engine(params), _engine(params)
    reqs = [_request(i) for i in range(len(SPECS))]
    for r in reqs[:4]:
        src.submit(r)                            # the fourth waits
    dst.submit(reqs[4])
    for _ in range(6):
        src.step()
        dst.step()
    assert reqs[3].t_admit is not None           # admitted mid-run
    moved = src.slots[0]
    assert moved is reqs[0] and not moved.done
    dst.import_slot(1, src.export_slot(0))
    src.slots[0], dst.slots[1] = None, moved
    src.run_until_done()
    dst.run_until_done()
    for r in reqs:
        assert r.done and r.output == want[r.req_id], r.req_id


def test_launch_counts_live_slots_and_positions_read(tmp_path):
    """A slot at offset 700 and one just admitted, two free: two slots
    fed, and attention reads each live length rounded up to its block
    (512 positions of 1 KB rows)."""
    cfg = reduced(get_config("qwen1.5-0.5b"), d_head=64, vocab_size=64)
    eng = _engine(init_lm(jax.random.PRNGKey(1), cfg), cfg, slots=4,
                  max_len=2048)
    row = cfg.n_kv_heads * cfg.d_head * 4
    block = _decode.kv_block(2048, row)
    assert row == 1024 and block == 512
    state = eng.export_slot(2)
    state["offset"], state["index"] = 700, np.int32(700)
    eng.import_slot(2, state)
    eng.slots[2] = Request(0, [1, 2, 3], max_new_tokens=100, output=[3])
    eng.step()                 # compiles; offsets 701 and 702 launched
    eng.submit(Request(1, [4, 5, 6], max_new_tokens=4))
    jax.profiler.start_trace(str(tmp_path))
    eng.step()
    jax.profiler.stop_trace()
    xplane = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    launches = [dict(e.stats)
                for plane in ProfileData.from_file(xplane[0]).planes
                for line in plane.lines for e in line.events
                if e.name == "serve.launch"]
    assert len(launches) == 1
    assert int(launches[0]["live"]) == 2
    assert int(launches[0]["kv_positions"]) == 1 * block + 2 * block
    assert int(launches[0]["device"]) == eng.device.id
