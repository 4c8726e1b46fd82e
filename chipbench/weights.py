"""Seeded weights of a dense decoder, by the published tensor names.

One jitted call makes every tensor on the device from the seed, in the type
the model is served in, with the layers stacked on a leading axis.  The
program and the plain reference are both handed these tensors; neither
makes its own.  Matrices are stored (in, out): ``y = x @ w``.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# (name, shape, std, mean) per tensor; norms scatter round 1 so that a
# reference that ignored a norm's scale would not agree.
Spec = Tuple[Tuple[str, Tuple[int, ...], float, float], ...]


def dims(m: Dict) -> Dict[str, int]:
    d, nq = m["hidden_size"], m["num_attention_heads"]
    return {"d": d, "L": m["num_hidden_layers"], "nq": nq,
            "nkv": m["num_key_value_heads"], "dh": m.get("head_dim") or d // nq,
            "ff": m["intermediate_size"], "V": m["vocab_size"]}


def spec(m: Dict) -> Spec:
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


def key_data(seed: int) -> np.ndarray:
    """A raw threefry key from a seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _generate(key, tensors: Spec, dtype: str) -> Dict[str, jax.Array]:
    out = {}
    for i, (name, shape, std, mean) in enumerate(tensors):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (z * std + mean).astype(dtype)
    return out


def generate(m: Dict, seed: int, device) -> Dict[str, jax.Array]:
    """Every tensor of model ``m`` (a config file's ``model``) on
    ``device``, in ``m["torch_dtype"]``."""
    key = jax.device_put(key_data(seed), device)
    return _generate(key, spec(m), m["torch_dtype"])
