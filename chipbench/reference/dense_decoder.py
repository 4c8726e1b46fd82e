"""Plain dense decoder: the forward pass of Qwen2 (Qwen1.5) and Granite-3.0
as the Hugging Face model code describes them, in float32 at the highest
matrix-multiplication precision, with no cache, kernel or batching.

Per layer, with x the residual stream of one sequence (T, d):

    h = rmsnorm(x) * input_layernorm
    q, k, v = h @ q_proj (+ q_bias), h @ k_proj (+ k_bias), h @ v_proj (+ v_bias)
    q, k = rope(q), rope(k)                    # rotate-half form, base rope_theta
    a = softmax(q k^T * attention_multiplier + causal mask) v
                                               # query head i reads kv head i // (nq/nkv)
    x = x + residual_multiplier * (a @ o_proj)
    h = rmsnorm(x) * post_attention_layernorm
    x = x + residual_multiplier * ((silu(h @ gate_proj) * (h @ up_proj)) @ down_proj)

with x0 = embed_tokens[tokens] * embedding_multiplier and logits =
(rmsnorm(x) * norm) @ embed_tokens^T / logits_scaling.  Qwen2 has biases on
q, k and v and no multipliers; Granite-3.0 has no biases and the four
multipliers, which the configuration file gives as run (its departures).

``mode="fp8"`` is the control: the same pass with every matrix-product
input rounded to float8 e4m3 (weights per layer and matrix, activations
per row, q/k/v and the attention probabilities per tensor), the precision
below the bfloat16 the models are served in.  Products of e4m3 values are
exact at the default precision, and the sums stay in float32.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 240.0     # largest finite value with 4 exponent, 3 mantissa bits
_ROWS = 512          # logits are formed this many positions at a time


def _fp8(x, axis=None):
    """``x`` rounded to 4 exponent and 3 mantissa bits after scaling its
    largest magnitude (over ``axis``, or the whole tensor) to 240, the
    largest such value; returns the rounded values and the scale, so that
    x ~ values * scale."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    rounded = jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                       mantissa_bits=3)
    return rounded, scale


def _matmul(spec, a, b, mode, a_axis=None):
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    a8, sa = _fp8(a, a_axis)
    b8, sb = _fp8(b)
    return jnp.einsum(spec, a8, b8) * sa * sb


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, positions, theta):
    """x: (T, H, dh).  cos/sin of cat(freqs, freqs), rotate_half(x) =
    cat(-x2, x1)."""
    dh = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    freqs = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def _layer(x, lw, m, mode):
    T, d = x.shape
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    dh = m.get("head_dim") or d // nq
    f32 = {k: v.astype(jnp.float32) for k, v in lw.items()}
    positions = jnp.arange(T)

    h = _rmsnorm(x, f32["input_layernorm"], m["rms_norm_eps"])
    q = _matmul("td,de->te", h, f32["q_proj"], mode, -1)
    k = _matmul("td,de->te", h, f32["k_proj"], mode, -1)
    v = _matmul("td,de->te", h, f32["v_proj"], mode, -1)
    if m["qkv_bias"]:
        q, k, v = q + f32["q_bias"], k + f32["k_bias"], v + f32["v_bias"]
    q = _rope(q.reshape(T, nq, dh), positions, m["rope_theta"])
    k = _rope(k.reshape(T, nkv, dh), positions, m["rope_theta"])
    v = v.reshape(T, nkv, dh)
    k = jnp.repeat(k, nq // nkv, axis=1)
    v = jnp.repeat(v, nq // nkv, axis=1)
    scale = m.get("attention_multiplier", dh ** -0.5)
    scores = _matmul("thd,shd->hts", q, k, mode) * scale
    causal = positions[:, None] >= positions[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    a = _matmul("hts,shd->thd", probs, v, mode).reshape(T, nq * dh)
    res = m.get("residual_multiplier", 1.0)
    x = x + res * _matmul("te,ed->td", a, f32["o_proj"], mode, -1)

    h = _rmsnorm(x, f32["post_attention_layernorm"], m["rms_norm_eps"])
    gate = _matmul("td,df->tf", h, f32["gate_proj"], mode, -1)
    up = _matmul("td,df->tf", h, f32["up_proj"], mode, -1)
    down = _matmul("tf,fd->td", jax.nn.silu(gate) * up, f32["down_proj"], mode, -1)
    return x + res * down


_LAYER_KEYS = ("input_layernorm", "q_proj", "k_proj", "v_proj", "q_bias",
               "k_bias", "v_bias", "o_proj", "post_attention_layernorm",
               "gate_proj", "up_proj", "down_proj")


def hidden(w: Dict[str, jax.Array], tokens, m: Dict, mode: str = "f32"):
    """Final normed hidden states (T, d) of one sequence, float32."""
    emb = jnp.take(w["embed_tokens"], tokens, axis=0).astype(jnp.float32)
    x = emb * m.get("embedding_multiplier", 1.0)
    layers = {k: w[k] for k in _LAYER_KEYS if k in w}

    def body(x, lw):
        return _layer(x, lw, m, mode), None

    x, _ = jax.lax.scan(body, x, layers)
    return _rmsnorm(x, w["norm"].astype(jnp.float32), m["rms_norm_eps"])


def _logits(h, table, m, mode):
    return (_matmul("td,vd->tv", h, table, mode, -1)
            / m.get("logits_scaling", 1.0))


def _by_rows(fn, *arrays):
    """``fn`` over blocks of ``_ROWS`` positions, so that a (T, vocab)
    block of logits is never whole."""
    T = arrays[0].shape[0]
    rows = min(_ROWS, T)
    blocks = [a.reshape(T // rows, rows, *a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda xs: fn(*xs), tuple(blocks))
    return jax.tree.map(lambda o: o.reshape(T, *o.shape[2:]), out)


def _static(m: Dict) -> Tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool, str))))


@functools.partial(jax.jit, static_argnames=("model", "mode"))
def _full_logits(w, tokens, model: Tuple, mode: str):
    m = dict(model)
    return _logits(hidden(w, tokens, m, mode),
                   w["embed_tokens"].astype(jnp.float32), m, mode)


def logits(w: Dict[str, jax.Array], tokens, m: Dict, mode: str = "f32"):
    """(T, vocab) logits of one sequence; for small sizes and tests."""
    return _full_logits(w, tokens, _static(m), mode)


@functools.partial(jax.jit, static_argnames=("model", "control"))
def _compare(w, tokens, targets, model: Tuple, control: bool):
    m = dict(model)
    table = w["embed_tokens"].astype(jnp.float32)
    h = hidden(w, tokens, m, "f32")
    hc = hidden(w, tokens, m, "fp8") if control else h

    def rows(h_blk, hc_blk, t_blk):
        ref = _logits(h_blk, table, m, "f32")
        out = {"best": ref.max(-1),
               "target": jnp.take_along_axis(ref, t_blk[:, None], -1)[:, 0]}
        if control:
            choice = _logits(hc_blk, table, m, "fp8").argmax(-1)
            out["control"] = jnp.take_along_axis(ref, choice[:, None], -1)[:, 0]
        return out

    return _by_rows(rows, h, hc, targets)


def compare(w: Dict[str, jax.Array], tokens, targets, m: Dict,
            control: bool = False) -> Dict[str, jax.Array]:
    """For each position t of ``tokens`` (T,), with T a multiple of 512 or
    under it: the reference's largest logit (``best``), its logit of
    ``targets[t]`` (``target``), and with ``control`` its logit of the
    token that the float8 pass puts first (``control``)."""
    return _compare(w, tokens, targets, _static(m), control)
