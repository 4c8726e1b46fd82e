"""The benchmark's one seam to the system under test: a configuration file
becomes the program's ``ModelConfig``, the seeded tensors become its
parameter tree, and each replica is a ``ServeEngine`` on its own device."""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax

from repro.configs import get_config
from repro.models import ModelConfig, init_lm
from repro.serve import ServeEngine

from chipbench.weights import dims


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
    """The program's parameter tree holding the seeded tensors ``w``.
    Raises where the program's own tree has another layout."""
    attn = {name: {"w": w[f"{name[1]}_proj"]} for name in ("wq", "wk", "wv",
                                                          "wo")}
    if cfg.qkv_bias:
        for name in ("wq", "wk", "wv"):
            attn[name]["b"] = w[f"{name[1]}_bias"]
    tree = {
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
    want = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    if jax.tree.structure(want) != jax.tree.structure(tree):
        raise ValueError(f"the program's parameter tree is "
                         f"{jax.tree.structure(want)}")
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(tree)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise ValueError(f"{jax.tree_util.keystr(path)}: program wants "
                             f"{a.shape} {a.dtype}, got {b.shape} {b.dtype}")
    return tree


def engine(config: Dict, cfg: ModelConfig, params: Dict, device) -> ServeEngine:
    e = config["engine"]
    # eos_id -1: no token ends a request, so outputs keep their drawn length.
    return ServeEngine(cfg, params, int(e["slots"]), int(e["max_len"]),
                       eos_id=-1, device=device)
