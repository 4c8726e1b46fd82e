"""The plain references against the program's prefill-then-decode logits at
a small size on seeded weights, and the float8 control against both."""

import dataclasses

import jax
import numpy as np
import pytest

from chipbench import program, weights
from chipbench.reference import dense_decoder as ref
from conftest import TINY_MODEL
from repro.serve import Request

QWEN_LIKE = dict(TINY_MODEL, num_key_value_heads=4, qkv_bias=True,
                 rope_theta=1_000_000.0)
GRANITE_LIKE = dict(TINY_MODEL, num_key_value_heads=2, qkv_bias=False,
                    attention_multiplier=32 ** -0.5, embedding_multiplier=1.0,
                    residual_multiplier=1.0, logits_scaling=1.0)


def _served_logits(model, registry, seed=3, prompt_len=9, new_tokens=20):
    """One request through ``ServeEngine``: its slot's logits at every step
    (prompt tokens one per step, then its own greedy tokens)."""
    config = {"registry": registry, "model": model,
              "engine": {"slots": 4, "max_len": 64}}
    dev = jax.devices()[0]
    w = weights.generate(model, seed, dev)
    cfg = program.model_config(config)
    eng = program.engine(config, cfg, program.program_params(w, cfg), dev)
    prompt = np.random.default_rng(seed).integers(0, model["vocab_size"],
                                                  prompt_len).tolist()
    req = Request(0, prompt, max_new_tokens=new_tokens)
    eng.submit(req)
    rows = []
    while not req.done:
        rows.append(np.asarray(eng.step()[0, 0], np.float64))
    return w, req, np.stack(rows)


@pytest.mark.parametrize("model,registry", [(QWEN_LIKE, "qwen1.5-0.5b"),
                                            (GRANITE_LIKE, "granite-3-2b")],
                         ids=["qwen-like", "granite-like"])
def test_reference_matches_served_logits(model, registry):
    w, req, got = _served_logits(model, registry)
    fed = req.prompt + req.output[:-1]
    want = np.asarray(ref.logits(w, np.asarray(fed, np.int32), model),
                      np.float64)
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    # bf16 weights, activations and cache against float32: a few 1e-3.
    assert rel.max() < 0.03, rel.max()
    # Every served token is the reference's best or a near-tie of it.
    served = want[len(req.prompt) - 1:]
    gap = served.max(-1) - served[np.arange(len(req.output)), req.output]
    assert gap.max() < 0.05


def test_float8_control_is_further_off_than_the_program():
    w, req, got = _served_logits(QWEN_LIKE, "qwen1.5-0.5b")
    fed = np.asarray(req.prompt + req.output[:-1], np.int32)
    want = np.asarray(ref.logits(w, fed, QWEN_LIKE), np.float64)
    low = np.asarray(ref.logits(w, fed, QWEN_LIKE, mode="fp8"), np.float64)

    def err(x):
        return (np.linalg.norm(x - want, axis=-1)
                / np.linalg.norm(want, axis=-1)).max()

    assert err(low) > 3 * err(got)


def test_compare_reads_gaps_at_each_position():
    w = weights.generate(QWEN_LIKE, 5, jax.devices()[0])
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
    w = weights.generate(GRANITE_LIKE, 5, jax.devices()[0])
    tokens = np.arange(16, dtype=np.int32)
    base = np.asarray(ref.logits(w, tokens, GRANITE_LIKE))
    published = dict(GRANITE_LIKE, embedding_multiplier=12.0,
                     attention_multiplier=1 / 64, residual_multiplier=0.22,
                     logits_scaling=8.0)
    assert not np.allclose(np.asarray(ref.logits(w, tokens, published)), base)
    with pytest.raises(ValueError, match="embedding_multiplier"):
        program.model_config({"registry": "granite-3-2b", "model": published})


def test_program_tree_holds_the_seeded_tensors():
    cfg = program.model_config({"registry": "qwen1.5-0.5b",
                                "model": QWEN_LIKE})
    w = weights.generate(QWEN_LIKE, 9, jax.devices()[0])
    tree = program.program_params(w, cfg)
    assert tree["blocks"]["pos0"]["attn"]["wq"]["b"] is w["q_bias"]
    assert tree["blocks"]["pos0"]["ffn"]["w_down"]["w"] is w["down_proj"]
    with pytest.raises(ValueError, match="program wants"):
        program.program_params(w, dataclasses.replace(cfg, n_layers=3))
