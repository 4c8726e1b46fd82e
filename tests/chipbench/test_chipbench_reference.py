"""The plain references against the program's prefill-then-decode logits at
a small size on seeded weights, and the float8 control against both."""

import dataclasses
import hashlib
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from chipbench import arch, program, reference, weights
from chipbench.arch import dense_decoder as dense
from chipbench.reference import dense_decoder as ref
from conftest import TINY_MODEL
from repro.serve import Request

# One served case per file, fixtures/served/<case>.json: the architecture
# module, the registry configuration and a tiny model of that
# architecture.  A later architecture adds its case as one more file.
SERVED = {p.stem: json.loads(p.read_text()) for p in sorted(
    (Path(__file__).parent / "fixtures" / "served").glob("*.json"))}
QWEN_LIKE = SERVED["qwen-like"]["model"]
GRANITE_LIKE = SERVED["granite-like"]["model"]


def _served_logits(name, model, registry, seed=3, prompt_len=9,
                   new_tokens=20):
    """One request through ``ServeEngine``, with the architecture module
    ``name``: its slot's logits at each step at which it gained a token,
    which are the logits at positions len(prompt) - 1 onward, however the
    prompt was fed."""
    mod = arch.module(name)
    config = {"registry": registry, "model": model,
              "engine": {"slots": 4, "max_len": 64}}
    dev = jax.devices()[0]
    w = weights.generate(mod, model, seed, dev)
    cfg = mod.model_config(config)
    eng = program.engine(config, cfg, program.params(mod, w, cfg), dev)
    prompt = np.random.default_rng(seed).integers(0, model["vocab_size"],
                                                  prompt_len).tolist()
    req = Request(0, prompt, max_new_tokens=new_tokens)
    eng.submit(req)
    rows = []
    while not req.done:
        before = len(req.output)
        logits = eng.step()
        if len(req.output) > before:
            rows.append(np.asarray(logits[0, 0], np.float64))
    assert len(rows) == len(req.output) == new_tokens
    return w, req, np.stack(rows)


def _reference_rows(name, w, req, model, mode="f32"):
    """The reference's logits at the served positions: over the prompt and
    every served token but the last, from position len(prompt) - 1."""
    fed = np.asarray(req.prompt + req.output[:-1], np.int32)
    full = reference.module(name).logits(w, fed, model, mode=mode)
    return np.asarray(full, np.float64)[len(req.prompt) - 1:]


@pytest.mark.parametrize("case", sorted(SERVED))
def test_reference_matches_served_logits(case):
    name, model = SERVED[case]["arch"], SERVED[case]["model"]
    w, req, got = _served_logits(name, model, SERVED[case]["registry"])
    want = _reference_rows(name, w, req, model)
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    # bf16 weights, activations and cache against float32: a few 1e-3.
    assert rel.max() < 0.03, rel.max()
    # Every served token is the reference's best or a near-tie of it.
    gap = want.max(-1) - want[np.arange(len(req.output)), req.output]
    assert gap.max() < 0.05


def test_float8_control_is_further_off_than_the_program():
    w, req, got = _served_logits("dense_decoder", QWEN_LIKE, "qwen1.5-0.5b")
    want = _reference_rows("dense_decoder", w, req, QWEN_LIKE)
    low = _reference_rows("dense_decoder", w, req, QWEN_LIKE, mode="fp8")

    def err(x):
        return (np.linalg.norm(x - want, axis=-1)
                / np.linalg.norm(want, axis=-1)).max()

    assert err(low) > 3 * err(got)


def test_compare_reads_gaps_at_each_position():
    w = weights.generate(dense, QWEN_LIKE, 5, jax.devices()[0])
    tokens = np.random.default_rng(0).integers(0, 512, 64).astype(np.int32)
    full = np.asarray(ref.logits(w, tokens, QWEN_LIKE))
    targets = np.roll(tokens, -1)
    rows = jax.device_get(ref.compare(w, tokens, targets, QWEN_LIKE,
                                      control=True))
    np.testing.assert_allclose(rows["best"], full.max(-1), rtol=1e-5)
    np.testing.assert_allclose(rows["target"],
                               full[np.arange(64), targets], rtol=1e-5)
    assert np.all(rows["control"] <= rows["best"] + 1e-6)


def test_granite_multipliers_change_the_reference():
    """The published multipliers are applied where the file gives them; the
    program cannot run them and refuses such a file."""
    w = weights.generate(dense, GRANITE_LIKE, 5, jax.devices()[0])
    tokens = np.arange(16, dtype=np.int32)
    base = np.asarray(ref.logits(w, tokens, GRANITE_LIKE))
    published = dict(GRANITE_LIKE, embedding_multiplier=12.0,
                     attention_multiplier=1 / 64, residual_multiplier=0.22,
                     logits_scaling=8.0)
    assert not np.allclose(np.asarray(ref.logits(w, tokens, published)), base)
    with pytest.raises(ValueError, match="embedding_multiplier"):
        dense.model_config({"registry": "granite-3-2b", "model": published})


def test_program_tree_holds_the_seeded_tensors():
    cfg = dense.model_config({"registry": "qwen1.5-0.5b", "model": QWEN_LIKE})
    w = weights.generate(dense, QWEN_LIKE, 9, jax.devices()[0])
    tree = program.params(dense, w, cfg)
    assert tree["blocks"]["pos0"]["attn"]["wq"]["b"] is w["q_bias"]
    assert tree["blocks"]["pos0"]["ffn"]["w_down"]["w"] is w["down_proj"]
    with pytest.raises(ValueError, match="program wants"):
        program.params(dense, w, dataclasses.replace(cfg, n_layers=3))
    # a tree of another structure is refused too
    del tree["tail"]
    with pytest.raises(ValueError, match="parameter tree is"):
        program.params(SimpleNamespace(program_params=lambda w, cfg: tree),
                       w, cfg)


# sha256 over each tensor's name, shape and bfloat16 bits, in name order,
# at seed 2**31 + 5: what the weights read before the tensors' layout
# moved into the architecture module.
TINY_HASHES = {
    True: "fca906ef5d1fe77047f5023382ec3900c2a34693adcff702d9b399f10601520b",
    False: "a3924f757721e59e86650efa1fb740349cb7203c6d4412fa7f12ce20e1786596",
}


@pytest.mark.parametrize("bias", [True, False], ids=["qkv-bias", "no-bias"])
def test_seeded_tensors_read_as_before(bias):
    w = weights.generate(dense, dict(TINY_MODEL, qkv_bias=bias), 2 ** 31 + 5,
                         jax.devices()[0])
    h = hashlib.sha256()
    for name in sorted(w):
        a = np.asarray(w[name])
        h.update(name.encode())
        h.update(str(a.shape).encode())
        h.update(a.view(np.uint16).tobytes())
    assert h.hexdigest() == TINY_HASHES[bias]
