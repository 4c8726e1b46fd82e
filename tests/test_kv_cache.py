"""The lane-dense KV cache through every cache user: continuous-batching
decode against a full forward, prefill-into-cache against decode alone, a
slot moved between engines, and the decode kernel inside the layer scan."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import decode_attention as _decode
from repro.kernels import ops
from repro.models import forward, init_cache, init_lm, logits_fn, reduced
from repro.serve import Request, ServeEngine
from repro.serve.engine import make_decode_step, make_prefill_step

KEY = jax.random.PRNGKey(0)

CONFIGS = {
    # GQA 4:1 with heads of 64: two heads share a 128-lane row tile.
    "granite": reduced(get_config("granite-3-2b"), n_heads=8, n_kv_heads=2,
                       d_head=64, vocab_size=64),
    # MHA with QKV bias.
    "qwen": reduced(get_config("qwen1.5-0.5b"), d_head=64, vocab_size=64),
    # Mamba2 layers and a weight-shared attention block every two layers;
    # five layers leave a tail layer with its own shared-attention cache.
    "zamba2": reduced(get_config("zamba2-7b"), n_layers=5, vocab_size=64),
}


def _full_logits(params, cfg, tokens):
    hidden, _, _ = forward(params, jnp.asarray([tokens], jnp.int32), cfg)
    return np.asarray(logits_fn(params, hidden, cfg)[0])


def _serve(cfg, params, prompts, arrive, max_new, slots=2, max_len=48):
    """Requests submitted at the steps ``arrive`` names, so the slots hold
    caches of different lengths.  Returns each request's logits, one row
    per token it was fed, and the finished requests."""
    eng = ServeEngine(cfg, params, batch_slots=slots, max_len=max_len,
                      eos_id=-1)
    reqs = [Request(i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, max_new))]
    rows = {r.req_id: [] for r in reqs}
    step = 0
    while step < 500 and (any(eng.slots) or eng.queue or
                          any(a >= step for a in arrive)):
        for r, a in zip(reqs, arrive):
            if a == step:
                eng.submit(r)
        # The step this call emits: the one in flight, or with none, the
        # first it launches, over the slots as admission leaves them.
        if eng._flight is not None:
            live = {i: r for i, r in enumerate(eng._flight.reqs)
                    if r is not None}
        else:
            live = {i: s for i, s in enumerate(eng.slots) if s is not None}
            free = [i for i, s in enumerate(eng.slots) if s is None]
            live.update(zip(free, eng.queue))
        logits = eng.step()
        step += 1
        if logits is None:
            continue
        for i, req in live.items():
            rows[req.req_id].append(np.asarray(logits[i, 0]))
    return {k: np.stack(v) for k, v in rows.items()}, reqs


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_continuous_batching_matches_full_forward(name):
    cfg = CONFIGS[name]
    params = init_lm(KEY, cfg)
    prompts = [[5, 6, 7, 8, 9], [11, 12], [3, 4, 5, 6, 7, 8, 9, 10], [2, 3, 4]]
    rows, reqs = _serve(cfg, params, prompts, arrive=[0, 3, 4, 9],
                        max_new=[6, 9, 4, 5])
    for req in reqs:
        assert req.done and len(req.output) == req.max_new_tokens
        fed = req.prompt + req.output[:-1]
        np.testing.assert_allclose(rows[req.req_id],
                                   _full_logits(params, cfg, fed),
                                   atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("per_slot", [False, True])
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_prefill_into_cache_then_decode_equals_decode_alone(name, per_slot):
    """S rows written at once (scalar offset, or one offset per slot), then
    decode, against the same tokens fed one at a time."""
    cfg = CONFIGS[name]
    params = init_lm(KEY, cfg)
    B, S, T, steps = 2, 6, 16, 4
    prompt = jax.random.randint(KEY, (B, S), 1, cfg.vocab_size)
    more = jax.random.randint(jax.random.fold_in(KEY, 1), (B, steps), 1,
                              cfg.vocab_size)
    decode = jax.jit(make_decode_step(cfg))

    def run(cache, first):
        out = []
        for t in range(steps):
            cache, lg = decode(params, cache, more[:, t:t + 1])
            out.append(np.asarray(lg[:, 0]))
        return np.stack([np.asarray(first)] + out), cache

    if per_slot:
        cache = init_cache(cfg, B, T, per_slot_index=True)
        hidden, cache, _ = forward(params, prompt, cfg, cache=cache)
        first = logits_fn(params, hidden[:, -1], cfg)
    else:
        cache, lg = jax.jit(make_prefill_step(cfg, T))(params,
                                                       {"tokens": prompt})
        first = lg[:, 0]
    got, got_cache = run(cache, first)

    cache = init_cache(cfg, B, T, per_slot_index=per_slot)
    for t in range(S):
        cache, lg = decode(params, cache, prompt[:, t:t + 1])
    want, want_cache = run(cache, lg[:, 0])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-3)
    np.testing.assert_array_equal(np.asarray(got_cache["index"]),
                                  np.asarray(want_cache["index"]))


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_moved_slot_continues_bit_identically(name):
    """A session exported mid-generation and imported into a second engine
    gives the same logits and tokens there as on the engine that kept it."""
    cfg = CONFIGS[name]
    params = init_lm(KEY, cfg)

    def engine():
        return ServeEngine(cfg, params, batch_slots=2, max_len=32, eos_id=-1)

    stay, src, dst = engine(), engine(), engine()
    for eng in (stay, src):
        eng.submit(Request(0, prompt=[5, 6, 7, 8], max_new_tokens=10))
        eng.submit(Request(1, prompt=[9, 10], max_new_tokens=3))
    dst.submit(Request(7, prompt=[3, 2, 1], max_new_tokens=12))
    for _ in range(7):
        stay.step()
        src.step()
    dst.step()
    assert dst.slots[1] is None
    moved = src.slots[0]
    dst.import_slot(1, src.export_slot(0))
    dst.slots[1], src.slots[0] = moved, None
    # src's step in flight at the move holds the session's eighth position;
    # dst's does not hold it.
    np.testing.assert_array_equal(np.asarray(src.step()[0, 0]),
                                  np.asarray(stay.step()[0, 0]))
    dst.step()
    for _ in range(5):
        want = np.asarray(stay.step()[0, 0])
        got = np.asarray(dst.step()[1, 0])
        np.testing.assert_array_equal(got, want)
    kept = next(r for r in stay.finished + stay.slots
                if r is not None and r.req_id == 0)
    assert moved.output == kept.output


@pytest.mark.parametrize("name", ["granite", "qwen"])
def test_decode_kernel_in_the_layer_scan_matches_the_oracle(name, monkeypatch):
    """The decode step with the Pallas kernel (interpreted) reading each
    layer of the scan's stacked cache gives the oracle's logits."""
    cfg = CONFIGS[name]
    params = init_lm(KEY, cfg)
    prompts = [[5, 6, 7, 8, 9], [11, 12, 13]]
    want, _ = _serve(cfg, params, prompts, arrive=[0, 2], max_new=[5, 6],
                     max_len=64)

    def kernel(q, k_stack, v_stack, layer, kv_len):
        return _decode.stacked_decode_attention(q, k_stack, v_stack, layer,
                                                kv_len, block_bytes=1 << 13,
                                                interpret=True)
    monkeypatch.setattr(ops, "stacked_decode_attention", kernel)
    got, _ = _serve(cfg, params, prompts, arrive=[0, 2], max_new=[5, 6],
                    max_len=64)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], atol=1e-5, rtol=1e-5)


def test_self_attention_caches_are_lane_dense():
    cfg = CONFIGS["zamba2"]
    cache = jax.eval_shape(lambda: init_cache(cfg, 3, 16, per_slot_index=True))
    width = cfg.n_kv_heads * cfg.d_head
    assert cache["shared"]["attn"]["k"].shape == (2, 3, 16, width)
    assert [c["attn"]["v"].shape for c in cache["tail_shared"]] == [(3, 16, width)]
    deeper = dataclasses.replace(CONFIGS["granite"], n_layers=3)
    stack = jax.eval_shape(lambda: init_cache(deeper, 4, 8))
    assert stack["blocks"]["pos0"]["attn"]["k"].shape == (3, 4, 8, 128)
