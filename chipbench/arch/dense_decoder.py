"""A dense decoder (Qwen2 / Qwen1.5, Granite-3.0): pre-norm GQA attention
with RoPE and a SwiGLU feed-forward in every layer, tied embeddings.

Tensors go by the published names, with the layers stacked on a leading
axis; matrices are stored (in, out): ``y = x @ w``.

Counts: operations and bytes that one decode step needs, counted from the
tokens it processed and the positions each attended, never from the
program's shapes or ``max_len``: the same work reads the same whatever
implements it.

Per token at context c (positions 0..c-1 attended, its own included):
  FLOPs  2 x (matrix parameters of every layer + the output head d x V)
         + 4 x layers x query heads x head size x c   (q.k and p.v)
Per step over tokens with contexts c_i, in bytes of the served type:
  weights once (every parameter; the tied table counts once), keys and
  values read over each token's context, keys and values written for each
  token, float32 logits written for each token.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable, Tuple

import jax

from chipbench.counts import DTYPE_BYTES, LOGIT_BYTES
from chipbench.weights import Spec
from repro.configs import get_config
from repro.models import ModelConfig


def dims(m: Dict) -> Dict[str, int]:
    d, nq = m["hidden_size"], m["num_attention_heads"]
    return {"d": d, "L": m["num_hidden_layers"], "nq": nq,
            "nkv": m["num_key_value_heads"], "dh": m.get("head_dim") or d // nq,
            "ff": m["intermediate_size"], "V": m["vocab_size"]}


# ------------------------------------------------------------- tensors --
def spec(m: Dict) -> Spec:
    """Norms scatter round 1, so that a reference that ignored a norm's
    scale would not agree."""
    if not m["tie_word_embeddings"]:
        raise ValueError("only tied input and output embeddings are built")
    k = dims(m)
    d, L, ff = k["d"], k["L"], k["ff"]
    q, kv = k["nq"] * k["dh"], k["nkv"] * k["dh"]
    out = [("embed_tokens", (k["V"], d), d ** -0.5, 0.0),
           ("input_layernorm", (L, d), 0.1, 1.0),
           ("q_proj", (L, d, q), d ** -0.5, 0.0),
           ("k_proj", (L, d, kv), d ** -0.5, 0.0),
           ("v_proj", (L, d, kv), d ** -0.5, 0.0)]
    if m["qkv_bias"]:
        out += [("q_bias", (L, q), 0.1, 0.0), ("k_bias", (L, kv), 0.1, 0.0),
                ("v_bias", (L, kv), 0.1, 0.0)]
    out += [("o_proj", (L, q, d), q ** -0.5, 0.0),
            ("post_attention_layernorm", (L, d), 0.1, 1.0),
            ("gate_proj", (L, d, ff), d ** -0.5, 0.0),
            ("up_proj", (L, d, ff), d ** -0.5, 0.0),
            ("down_proj", (L, ff, d), ff ** -0.5, 0.0),
            ("norm", (d,), 0.1, 1.0)]
    return tuple(out)


# ------------------------------------------------------------- program --
def model_config(config: Dict) -> ModelConfig:
    """The registry's configuration with the sizes of the file's ``model``.

    The program has no embedding, residual or logit multipliers and scales
    attention by 1/sqrt(head size); a file that asks for anything else
    cannot be run as written and is refused."""
    m = config["model"]
    k = dims(m)
    as_run = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
              "logits_scaling": 1.0, "attention_multiplier": k["dh"] ** -0.5}
    for key, value in as_run.items():
        if not math.isclose(m.get(key, value), value):
            raise ValueError(f"{config['registry']}: the program runs "
                             f"{key}={value}, the file says {m[key]}")
    if m["hidden_act"] != "silu":
        raise ValueError(f"hidden_act {m['hidden_act']!r} is not SwiGLU")
    return dataclasses.replace(
        get_config(config["registry"]), n_layers=k["L"], d_model=k["d"],
        n_heads=k["nq"], n_kv_heads=k["nkv"], d_head=k["dh"], d_ff=k["ff"],
        vocab_size=k["V"], qkv_bias=m["qkv_bias"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=m["tie_word_embeddings"],
        param_dtype=m["torch_dtype"], compute_dtype=m["torch_dtype"])


def program_params(w: Dict[str, jax.Array], cfg: ModelConfig) -> Dict:
    """The program's tree: one stacked block of attention and FFN."""
    attn = {name: {"w": w[f"{name[1]}_proj"]} for name in ("wq", "wk", "wv",
                                                          "wo")}
    if cfg.qkv_bias:
        for name in ("wq", "wk", "wv"):
            attn[name]["b"] = w[f"{name[1]}_bias"]
    return {
        "embed": {"embedding": w["embed_tokens"]},
        "blocks": {"pos0": {
            "norm1": {"scale": w["input_layernorm"]},
            "attn": attn,
            "norm2": {"scale": w["post_attention_layernorm"]},
            "ffn": {"w_gate": {"w": w["gate_proj"]}, "w_up": {"w": w["up_proj"]},
                    "w_down": {"w": w["down_proj"]}},
        }},
        "tail": [],
        "final_norm": {"scale": w["norm"]},
    }


# -------------------------------------------------------------- counts --
def parameter_count(m: Dict) -> int:
    return sum(math.prod(shape) for _, shape, _, _ in spec(m))


def matmul_params_per_token(m: Dict) -> int:
    """Parameters a token multiplies through: every layer's matrices and
    the output head (the embedding lookup is a gather, not a product)."""
    k = dims(m)
    q, kv = k["nq"] * k["dh"], k["nkv"] * k["dh"]
    per_layer = k["d"] * (q + 2 * kv) + q * k["d"] + 3 * k["d"] * k["ff"]
    return k["L"] * per_layer + k["d"] * k["V"]


def token_flops(m: Dict, context: int) -> float:
    k = dims(m)
    return (2.0 * matmul_params_per_token(m)
            + 4.0 * k["L"] * k["nq"] * k["dh"] * context)


def kv_bytes_per_position(m: Dict) -> int:
    k = dims(m)
    return 2 * k["L"] * k["nkv"] * k["dh"] * DTYPE_BYTES[m["torch_dtype"]]


def session_bytes(m: Dict, positions: int) -> int:
    """A session's keys and values: every layer's, at each position."""
    return kv_bytes_per_position(m) * positions


def step_work(m: Dict, contexts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step over tokens at ``contexts``."""
    contexts = list(contexts)
    if not contexts:
        return 0.0, 0.0
    width = DTYPE_BYTES[m["torch_dtype"]]
    kv = kv_bytes_per_position(m)
    flops = sum(token_flops(m, c) for c in contexts)
    nbytes = (parameter_count(m) * width
              + kv * sum(contexts)                  # read, own position too
              + kv * len(contexts)                  # written
              + LOGIT_BYTES * dims(m)["V"] * len(contexts))
    return flops, float(nbytes)
