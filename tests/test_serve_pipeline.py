"""One decode step in flight: ``ServeEngine.step`` launches step n+1
before it reads step n's tokens, and a generating slot is fed its token on
the device.  Every request's greedy tokens are those a serial decode of
it alone gives, through admission mid-run, ends by length, a stop on
``eos_id`` (whose token in flight is discarded and counted) and a move
while both engines have a step in flight, for attention, SSM and xLSTM
blocks.  Sampled tokens are the draws of each request's own keys, and
each ``step()`` emits one step's tokens and opens its phases once, in
order."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import init_cache, init_lm, reduced
from repro.serve import Request, ServeEngine
from repro.serve.engine import NO_TOKEN, make_decode_step

CFG = reduced(get_config("qwen1.5-0.5b"), vocab_size=64)
SLOTS, MAX_LEN = 3, 48
# (prompt, max_new_tokens, step submitted at)
SPECS = [([5, 6, 7, 8], 10, 0), ([9, 10], 3, 0), ([3, 2, 1], 12, 0),
         ([4, 4], 6, 2), ([1, 2, 3, 4, 5], 7, 5), ([7], 1, 6),
         ([8, 1, 8], 5, 9)]
PHASES = ["serve.feed", "serve.launch", "serve.wait", "serve.emit"]


@pytest.fixture(scope="module")
def params():
    return init_lm(jax.random.PRNGKey(0), CFG)


def _engine(params, cfg=CFG, eos_id=-1, **kw):
    return ServeEngine(cfg, params, batch_slots=SLOTS, max_len=MAX_LEN,
                       eos_id=eos_id, **kw)


def _serial(params, prompt, new, cfg=CFG, eos_id=-1):
    """One request decoded alone in slot 0, a step read before the next is
    launched: the decode step fed the prompt one token a step, then the
    best token of the step before."""
    decode = jax.jit(make_decode_step(cfg))
    cache = init_cache(cfg, SLOTS, MAX_LEN, per_slot_index=True)
    fed, out = list(prompt), []
    for pos in range(MAX_LEN):
        tokens = np.full((SLOTS, 1), NO_TOKEN, np.int32)
        tokens[0, 0] = fed[pos]
        cache, logits = decode(params, cache, jnp.asarray(tokens))
        if pos + 1 < len(prompt):
            continue
        out.append(int(np.argmax(np.asarray(logits[0, 0]))))
        fed.append(out[-1])
        if (len(out) >= new or out[-1] == eos_id
                or pos + 1 >= MAX_LEN - 1):
            return out
    return out


def _requests(specs=SPECS):
    return [Request(i, list(p), max_new_tokens=n)
            for i, (p, n, _) in enumerate(specs)]


def _drive(eng, reqs, specs=SPECS, on_step=lambda logits: None):
    """Submit each request at its step and call ``step()`` until nothing
    is queued, held or in flight."""
    calls = 0
    while (calls < 500 and (any(a >= calls for _, _, a in specs)
                            or eng.queue or eng._flight is not None
                            or any(s is not None for s in eng.slots))):
        for r, (_, _, at) in zip(reqs, specs):
            if at == calls:
                eng.submit(r)
        on_step(eng.step())
        calls += 1


def _trace_events(path):
    xplane = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for plane in ProfileData.from_file(xplane[0]).planes
                   for line in plane.lines for e in line.events
                   if e.name.startswith("serve.")), key=lambda e: e[1])


def test_requests_decode_as_alone_through_admission_and_length_ends(params):
    reqs = _requests()
    eng = _engine(params)
    _drive(eng, reqs)
    assert eng._flight is None
    for r, (prompt, new, _) in zip(reqs, SPECS):
        assert r.done and r.output == _serial(params, prompt, new), r.req_id
        assert len(r.output) == new
    assert sorted(r.req_id for r in eng.finished) == list(range(len(SPECS)))


def test_eos_stop_discards_and_counts_the_token_in_flight(params, tmp_path):
    free = [_serial(params, p, n) for p, n, _ in SPECS]
    eos = free[0][3]                          # request 0 stops at or before 3
    want = [_serial(params, p, n, eos_id=eos) for p, n, _ in SPECS]
    stopped = sum(w[-1] == eos and len(w) < n
                  for w, (_, n, _) in zip(want, SPECS))
    assert stopped >= 1
    eng = _engine(params, eos_id=eos)
    reqs = _requests()
    _drive(eng, _requests())                  # compiles
    jax.profiler.start_trace(str(tmp_path))
    _drive(eng, reqs)
    jax.profiler.stop_trace()
    for r, w in zip(reqs, want):
        assert r.done and r.output == w, r.req_id
    emits = [s for name, _, _, s in _trace_events(tmp_path)
             if name == "serve.emit"]
    assert sum(int(s["discarded"]) for s in emits) == stopped


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "zamba2-7b", "xlstm-1.3b"])
def test_a_move_while_both_engines_have_a_step_in_flight(arch):
    """A session exported mid-generation from an engine with a step in
    flight, into a free slot of another engine with a step in flight,
    serves what it serves alone; so do the sessions around it."""
    cfg = reduced(get_config(arch), vocab_size=64,
                  **({"n_layers": 5} if arch == "zamba2-7b" else {}))
    params = init_lm(jax.random.PRNGKey(1), cfg)
    specs = [([5, 6, 7, 8], 10, 0), ([9, 10, 11], 9, 0), ([3, 2, 1], 8, 0)]
    src, dst = _engine(params, cfg), _engine(params, cfg)
    reqs = _requests(specs)
    src.submit(reqs[0])
    src.submit(reqs[1])
    dst.submit(reqs[2])
    for _ in range(6):
        src.step()
        dst.step()
    moved = src.slots[0]
    assert moved is reqs[0] and moved.output and not moved.done
    assert src._flight is not None and dst._flight is not None
    held = len(moved.output)
    state = src.export_slot(0)
    assert len(moved.output) == held + 1      # the token in flight, read
    assert state["offset"] == len(moved.prompt) + len(moved.output) - 1
    dst.import_slot(1, state)
    src.slots[0], dst.slots[1] = None, moved
    src.run_until_done()
    dst.run_until_done()
    for r, (prompt, new, _) in zip(reqs, specs):
        assert r.done and r.output == _serial(params, prompt, new, cfg), \
            r.req_id


def test_sampled_tokens_are_the_per_slot_keys_draws(params):
    """At temperature 0.7 each token is the draw of the key folded from
    (rng_seed, req_id, tokens generated so far), from the logits of the
    step that emits it, as a per-slot sample on the host draws it."""
    eng = _engine(params, temperature=0.7, rng_seed=3)
    base = jax.random.PRNGKey(3)
    emitted, checked = [], []
    real = eng._emit

    def spy(step, tokens):
        emitted.append([(i, r, len(r.output)) for i, r in enumerate(step.reqs)
                        if r is not None and step.gen[i]])
        return real(step, tokens)
    eng._emit = spy

    def check(logits):
        for i, req, n in emitted.pop():
            key = jax.random.fold_in(jax.random.fold_in(base, req.req_id), n)
            want = jax.random.categorical(key, logits[i, 0] / 0.7, axis=-1)
            assert req.output[n] == int(want), (req.req_id, n)
            checked.append(n)

    reqs = _requests()
    _drive(eng, reqs, on_step=check)
    assert all(r.done for r in reqs)
    assert len(checked) == sum(n for _, n, _ in SPECS)
    greedy = [_serial(params, p, n) for p, n, _ in SPECS]
    assert [r.output for r in reqs] != greedy


def test_each_step_emits_one_step_and_opens_its_phases_once(params,
                                                            tmp_path):
    """Each call gives every request of one step its token, the best of
    that request's row of the logits it returns; its spans are the
    phases, once each and in order; and every launch of a busy run after
    the first is dispatched with the step before it still unread."""
    eng = _engine(params)
    _drive(eng, _requests())                  # compiles
    reqs = _requests()
    emitted, calls = [], []
    real = eng._emit

    def spy(step, tokens):
        emitted.append([(i, r, len(r.output)) for i, r in enumerate(step.reqs)
                        if r is not None and step.gen[i]])
        return real(step, tokens)
    eng._emit = spy

    def check(logits):
        assert len(emitted) == 1
        given = emitted.pop()
        best = np.argmax(np.asarray(logits[:, 0]), axis=-1)
        for i, req, n in given:
            assert len(req.output) == n + 1 and req.output[n] == best[i]
        total = sum(len(r.output) for r in reqs)
        calls.append(total)
        assert total - (calls[-2] if len(calls) > 1 else 0) == len(given)

    jax.profiler.start_trace(str(tmp_path))
    _drive(eng, reqs, on_step=check)
    jax.profiler.stop_trace()
    assert calls[-1] == sum(n for _, n, _ in SPECS)
    events = _trace_events(tmp_path)
    steps = [e for e in events if e[0] == "serve.step"]
    assert len(steps) == len(calls)
    launches = []
    for _, lo, hi, _ in steps:
        inner = [e for e in events if e[0] != "serve.step" and lo <= e[1] <= hi]
        assert [e[0] for e in inner] in (PHASES, ["serve.admit"] + PHASES)
        for a, b in zip(inner, inner[1:]):
            assert a[2] <= b[1]
        launches.append(inner[-3][3])
    assert int(launches[0]["ahead"]) == 0
    busy = [int(s["ahead"]) for s in launches[1:] if int(s["live"]) > 0]
    assert len(busy) >= len(steps) - 3 and all(a == 1 for a in busy)
