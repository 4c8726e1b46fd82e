"""A session's move in the program's own names: ``export_slot`` and
``import_slot`` open the spans ``serve.export`` / ``serve.import`` in a
profiler trace, and the slot programs they run carry the device scope
``serve_move``, which the decode program does not."""

import glob

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import init_cache, init_lm
from repro.models.config import reduced
from repro.serve import Request, ServeEngine
from repro.serve import engine as serve_engine
from repro.serve.trace import MOVE_SCOPE, SCOPES, SPANS

PROMPT = [3, 1, 4, 1, 5]
NEW = 12


def _engine(slots=2):
    cfg = reduced(get_config("qwen1.5-0.5b"), vocab_size=64)
    return ServeEngine(cfg, init_lm(jax.random.PRNGKey(0), cfg),
                       batch_slots=slots, max_len=48, eos_id=-1)


def _payload_bytes(state):
    arrays = {k: v for k, v in state.items() if k != "offset"}
    return sum(x.nbytes for x in jax.tree.leaves(arrays))


def _started(eng, steps):
    req = Request(7, list(PROMPT), max_new_tokens=NEW)
    eng.submit(req)
    for _ in range(steps):
        eng.step()
    return req


def _moved(src, dst, req, slot=0, to=1):
    """``req`` moved from ``src``'s ``slot`` into ``dst``'s ``to``."""
    state = src.export_slot(slot)
    dst.import_slot(to, state)
    src.slots[slot] = None
    dst.slots[to] = req
    return state


def test_move_spans_name_chip_bytes_and_positions(tmp_path):
    src, dst = _engine(), _engine()
    req = _started(src, len(PROMPT) + 3)
    dst.import_slot(0, dst.export_slot(0))        # compiles both programs
    jax.profiler.start_trace(str(tmp_path))
    state = _moved(src, dst, req)
    jax.block_until_ready(dst.cache)
    jax.profiler.stop_trace()
    xplane = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {e.name: dict(e.stats)
             for plane in ProfileData.from_file(xplane[0]).planes
             for line in plane.lines for e in line.events
             if e.name in ("serve.export", "serve.import")}
    assert {"serve.export", "serve.import"} <= set(SPANS)
    assert set(spans) == {"serve.export", "serve.import"}
    size = _payload_bytes(state)
    # eight steps read, and the one in flight when the move came
    assert size > 0 and state["offset"] == len(PROMPT) + 4
    for name, eng in (("serve.export", src), ("serve.import", dst)):
        assert int(spans[name]["device"]) == eng.device.id
        assert int(spans[name]["bytes"]) == size
        assert int(spans[name]["positions"]) == state["offset"]


@pytest.mark.parametrize("steps", [2, len(PROMPT) + 4])
def test_a_moved_session_continues_token_for_token(steps):
    """Moved mid-prompt or mid-answer, into another slot of another
    engine, the session serves what it serves unmoved."""
    alone = _engine()
    want = _started(alone, 0)
    alone.run_until_done()
    src, dst = _engine(), _engine()
    req = _started(src, steps)
    _moved(src, dst, req)
    dst.run_until_done()
    assert req.done and req.output == want.output
    assert len(req.output) == NEW


def test_move_scope_reaches_the_slot_programs_only():
    eng = _engine()
    state = {k: v for k, v in eng.export_slot(0).items() if k != "offset"}
    read = serve_engine._read_slot.lower(eng.cache, 0).compile().as_text()
    write = serve_engine._write_slot.lower(eng.cache, 0,
                                           state).compile().as_text()
    tokens = jnp.zeros((len(eng.slots), 1), jnp.int32)
    decode = jax.jit(serve_engine.make_decode_step(eng.cfg)).lower(
        eng.params, init_cache(eng.cfg, len(eng.slots), eng.max_len,
                               per_slot_index=True),
        tokens).compile().as_text()
    scope = f"/{MOVE_SCOPE}/"
    assert MOVE_SCOPE not in SCOPES
    assert scope in read and scope in write
    assert scope not in decode
    assert all(f"/{s}/" in decode for s in SCOPES)
