"""Grouped-query attention with RoPE/M-RoPE, KV cache, and cross-attention.

One path per mode.  Training, prefill and cross-attention run XLA ops:
`gqa_reference` below ``CHUNKED_ATTN_THRESHOLD``, above it the
online-softmax `flash_attention_jnp` (with its own flash backward) or,
for traced offsets and lengths, `chunked_attention`.  Cached decode
(S == 1) calls `repro.kernels.ops.stacked_decode_attention`: the Pallas
kernel when lowered for a TPU, its XLA oracle elsewhere.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.context import constrain

from .config import ModelConfig
from .layers import (apply_linear, apply_mrope, apply_rope, apply_rope_tables,
                     dtype_of, init_linear)

NEG_INF = -1e30

#: ``jax.named_scope`` names of the decode path's cache and attention work,
#: listed in ``repro.serve.trace.SCOPES``.  They reach the op-name metadata
#: of the compiled program's instructions and change no fusion.
KV_SCOPE = "serve_kv"
ATTN_SCOPE = "serve_attn"


def init_attention(key, cfg: ModelConfig, dtype, cross: bool = False) -> Dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, dh = cfg.d_model, cfg.d_head
    p = {
        "wq": init_linear(kq, d, cfg.n_heads * dh, dtype, bias=cfg.qkv_bias),
        "wk": init_linear(kk, d, cfg.n_kv_heads * dh, dtype, bias=cfg.qkv_bias),
        "wv": init_linear(kv, d, cfg.n_kv_heads * dh, dtype, bias=cfg.qkv_bias),
        "wo": init_linear(ko, cfg.n_heads * dh, d, dtype, scale=(cfg.n_heads * dh) ** -0.5),
    }
    return p


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict:
    """A self-attention KV cache, lane-dense: ``(batch, max_len, Hkv·Dh)``.

    A ``(…, Hkv, Dh)`` cache with heads of 64 would fill half of the TPU's
    128 lanes, so XLA keeps a stack of them position-minor and relays each
    layer to head-minor and back on every decode step; rows of ``Hkv·Dh``
    lanes need no padding and no relayout."""
    shape = (batch, max_len, cfg.n_kv_heads * cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def write_kv_rows(cache: jnp.ndarray, rows: jnp.ndarray, index,
                  layer=None) -> jnp.ndarray:
    """``cache`` with ``rows`` (B, S, Hkv·Dh) written at each sequence's
    write offset ``index``: a scalar (lockstep) or (B,) (continuous
    batching).  ``cache`` is one layer's (B, T, Hkv·Dh), or the stack
    (L, B, T, Hkv·Dh) written at ``layer``.  Only the new rows move, in one
    update, in place when the cache is donated; an offset past the end is
    clamped."""
    rows = rows.astype(cache.dtype)
    lead = () if layer is None else (jnp.asarray(layer, jnp.int32),)
    idx = jnp.asarray(index, jnp.int32)
    if idx.ndim == 0:
        zero = jnp.zeros((), jnp.int32)
        upd = rows if layer is None else rows[None]
        return jax.lax.dynamic_update_slice(cache, upd, (*lead, zero, idx, zero))
    B, S, _ = rows.shape
    pos = idx[:, None] + jnp.arange(S, dtype=jnp.int32)
    return cache.at[(*lead, jnp.arange(B)[:, None], pos)].set(
        rows, mode="clip", unique_indices=S == 1, indices_are_sorted=S == 1)


def kv_heads(cache: jnp.ndarray, layer, d_head: int) -> jnp.ndarray:
    """One layer of a lane-dense cache as (B, T, Hkv, Dh)."""
    c = cache if layer is None else cache[layer]
    return c.reshape(*c.shape[:-1], -1, d_head)


def _split_heads(x, n_heads, d_head):
    return x.reshape(*x.shape[:-1], n_heads, d_head)


def _rope(cfg: ModelConfig, x, positions, rope_cache=None):
    if rope_cache is not None:
        return apply_rope_tables(x, rope_cache)
    if positions is None:
        return x
    if cfg.mrope:
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return apply_rope(x, positions, cfg.rope_theta)


def gqa_reference(
    q: jnp.ndarray,            # (B, Sq, Hq, Dh)
    k: jnp.ndarray,            # (B, Sk, Hkv, Dh)
    v: jnp.ndarray,            # (B, Sk, Hkv, Dh)
    causal: bool,
    q_offset: int | jnp.ndarray = 0,   # absolute position of q[0] (decode)
    kv_len: Optional[jnp.ndarray] = None,  # #valid cache entries (decode)
) -> jnp.ndarray:
    """Pure-jnp GQA attention; fp32 softmax.  Oracle for the flash kernels."""
    B, Sq, Hq, Dh = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, Dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / (Dh ** 0.5)
    Sk = k.shape[1]
    qpos = jnp.arange(Sq)
    kpos = jnp.arange(Sk)
    mask = None  # broadcastable to (B, Sq, Sk); offsets/lengths may be per-row
    if causal:
        qoff = jnp.broadcast_to(jnp.asarray(q_offset), (B,))
        mask = (qoff[:, None, None] + qpos[None, :, None]) >= kpos[None, None, :]
    if kv_len is not None:
        kvl = jnp.broadcast_to(jnp.asarray(kv_len), (B,))
        valid = kpos[None, None, :] < kvl[:, None, None]
        mask = valid if mask is None else (mask & valid)
    if mask is not None:
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    if kv_len is not None:  # a row with no valid entry reads zeros
        out = jnp.where(kvl[:, None, None, None, None] > 0, out, 0.0)
    return out.reshape(B, Sq, Hq, Dh).astype(q.dtype)


def _flash_fwd_math(q, k, v, causal, q_offset, kv_len, q_chunk, k_chunk):
    """Online-softmax forward.  q: (B,Sq,Hq,Dh) → (out, lse (B,kv,G,Sq)).
    Pure XLA ops, one (q_chunk × k_chunk) score block at a time."""
    B, Sq, Hq, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    nq, nk = Sq // q_chunk, Sk // k_chunk
    scale = Dh ** -0.5
    qb = jnp.moveaxis(q.reshape(B, nq, q_chunk, Hkv, G, Dh), 1, 0).astype(jnp.float32)
    kb = jnp.moveaxis(k.reshape(B, nk, k_chunk, Hkv, Dh), 1, 0).astype(jnp.float32)
    vb = jnp.moveaxis(v.reshape(B, nk, k_chunk, Hkv, Dh), 1, 0).astype(jnp.float32)

    def per_q(qi, q_blk):  # q_blk: (B, qc, Hkv, G, Dh)
        qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def per_k(carry, inputs):
            m, l, acc = carry
            kj, k_blk, v_blk = inputs
            s = jnp.einsum("bqkgd,btkd->bkgqt", q_blk, k_blk) * scale
            kpos = kj * k_chunk + jnp.arange(k_chunk)
            mask = jnp.ones((q_chunk, k_chunk), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if kv_len is not None:
                mask &= (kpos < kv_len)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bkgqt,btkd->bkgqd", p, v_blk)
            return (m_new, l_new, acc_new), 0

        m0 = jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_chunk, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(per_k, (m0, l0, a0),
                                      (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]            # (B,kv,G,qc,Dh)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))                # (B,kv,G,qc)
        return jnp.moveaxis(out, 3, 1), lse

    with jax.named_scope("kscope_flash_fwd"):
        out, lse = jax.vmap(per_q)(jnp.arange(nq), qb)
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, Hq, Dh).astype(q.dtype)
    lse = jnp.moveaxis(lse, 0, 3).reshape(B, Hkv, G, Sq)        # (B,kv,G,nq·qc)
    return out, lse


def chunked_attention(q, k, v, *, causal, q_offset=0, kv_len=None,
                      q_chunk: int = 1024, k_chunk: int = 1024):
    """Forward-only online-softmax attention (prefill / encoder paths may
    carry traced offsets/lengths; training uses `flash_attention_jnp`)."""
    q_chunk = min(q_chunk, q.shape[1])
    k_chunk = min(k_chunk, k.shape[1])
    if q.shape[1] % q_chunk or k.shape[1] % k_chunk:
        return gqa_reference(q, k, v, causal, q_offset, kv_len)
    out, _ = _flash_fwd_math(q, k, v, causal, q_offset, kv_len, q_chunk, k_chunk)
    return out


# ---------------------------------------------------------- flash (train) --
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_jnp(q, k, v, causal: bool, q_chunk: int, k_chunk: int):
    """Flash attention with a flash *backward* (recompute probabilities per
    block from the saved log-sum-exp instead of storing them) — without this
    the scan backward stashes every (qc × kc) probability block and a 4k
    train step needs tens of GB per layer."""
    out, _ = _flash_fwd_math(q, k, v, causal, 0, None, q_chunk, k_chunk)
    return out


def _flash_fwd_rule(q, k, v, causal, q_chunk, k_chunk):
    out, lse = _flash_fwd_math(q, k, v, causal, 0, None, q_chunk, k_chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, q_chunk, k_chunk, res, dout):
    q, k, v, out, lse = res
    B, Sq, Hq, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    nq, nk = Sq // q_chunk, Sk // k_chunk
    scale = Dh ** -0.5
    f32 = jnp.float32
    qb = jnp.moveaxis(q.reshape(B, nq, q_chunk, Hkv, G, Dh), 1, 0).astype(f32)
    kb = jnp.moveaxis(k.reshape(B, nk, k_chunk, Hkv, Dh), 1, 0).astype(f32)
    vb = jnp.moveaxis(v.reshape(B, nk, k_chunk, Hkv, Dh), 1, 0).astype(f32)
    dob = jnp.moveaxis(dout.reshape(B, nq, q_chunk, Hkv, G, Dh), 1, 0).astype(f32)
    lseb = jnp.moveaxis(lse.reshape(B, Hkv, G, nq, q_chunk), 3, 0)  # (nq,B,kv,G,qc)
    # D_i = Σ_d dout·out  (rowwise), per q position.
    delta = jnp.einsum("bsqgd,bsqgd->bqgs",
                       dout.reshape(B, Sq, Hkv, G, Dh).astype(f32),
                       out.reshape(B, Sq, Hkv, G, Dh).astype(f32))  # (B,kv,G,Sq)
    deltab = jnp.moveaxis(delta.reshape(B, Hkv, G, nq, q_chunk), 3, 0)

    def mask_for(qi, kj):
        qpos = qi * q_chunk + jnp.arange(q_chunk)
        kpos = kj * k_chunk + jnp.arange(k_chunk)
        return qpos[:, None] >= kpos[None, :]

    # Pass 1 — dq: vmap over q blocks, scan over k blocks.
    def dq_per_q(qi, q_blk, do_blk, lse_blk, dl_blk):
        def body(dq_acc, inputs):
            kj, k_blk, v_blk = inputs
            s = jnp.einsum("bqkgd,btkd->bkgqt", q_blk, k_blk) * scale
            if causal:
                s = jnp.where(mask_for(qi, kj)[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lse_blk[..., None])
            dp = jnp.einsum("bqkgd,btkd->bkgqt", do_blk, v_blk)
            ds = p * (dp - dl_blk[..., None])
            dq_acc = dq_acc + jnp.einsum("bkgqt,btkd->bqkgd", ds, k_blk) * scale
            return dq_acc, 0
        dq0 = jnp.zeros_like(q_blk)
        dq_blk, _ = jax.lax.scan(body, dq0, (jnp.arange(nk), kb, vb))
        return dq_blk

    with jax.named_scope("kscope_flash_bwd"):
        dq = jax.vmap(dq_per_q)(jnp.arange(nq), qb, dob, lseb, deltab)
    dq = jnp.moveaxis(dq, 0, 1).reshape(B, Sq, Hq, Dh).astype(q.dtype)

    # Pass 2 — dk/dv: vmap over k blocks, scan over q blocks.
    def dkv_per_k(kj, k_blk, v_blk):
        def body(carry, inputs):
            dk_acc, dv_acc = carry
            qi, q_blk, do_blk, lse_blk, dl_blk = inputs
            s = jnp.einsum("bqkgd,btkd->bkgqt", q_blk, k_blk) * scale
            if causal:
                s = jnp.where(mask_for(qi, kj)[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lse_blk[..., None])
            dv_acc = dv_acc + jnp.einsum("bkgqt,bqkgd->btkd", p, do_blk)
            dp = jnp.einsum("bqkgd,btkd->bkgqt", do_blk, v_blk)
            ds = p * (dp - dl_blk[..., None])
            dk_acc = dk_acc + jnp.einsum("bkgqt,bqkgd->btkd", ds, q_blk) * scale
            return (dk_acc, dv_acc), 0
        z = jnp.zeros_like(k_blk)
        (dk_blk, dv_blk), _ = jax.lax.scan(
            body, (z, jnp.zeros_like(v_blk)),
            (jnp.arange(nq), qb, dob, lseb, deltab))
        return dk_blk, dv_blk

    with jax.named_scope("kscope_flash_bwd"):
        dk, dv = jax.vmap(dkv_per_k)(jnp.arange(nk), kb, vb)
    dk = jnp.moveaxis(dk, 0, 1).reshape(B, Sk, Hkv, Dh).astype(k.dtype)
    dv = jnp.moveaxis(dv, 0, 1).reshape(B, Sk, Hkv, Dh).astype(v.dtype)
    return dq, dk, dv


flash_attention_jnp.defvjp(_flash_fwd_rule, _flash_bwd_rule)


#: Sequences at or above this length use the online-softmax path.
CHUNKED_ATTN_THRESHOLD = 2048
_Q_CHUNK = 1024
_K_CHUNK = 1024


def _self_attention_math(q, k, v, causal, q_offset=0, kv_len=None):
    Sq, Sk = q.shape[1], k.shape[1]
    if Sq < CHUNKED_ATTN_THRESHOLD and Sk <= 2 * CHUNKED_ATTN_THRESHOLD:
        return gqa_reference(q, k, v, causal, q_offset, kv_len)
    qc, kc = min(_Q_CHUNK, Sq), min(_K_CHUNK, Sk)
    static_extras = isinstance(q_offset, int) and kv_len is None
    if static_extras and q_offset == 0 and Sq % qc == 0 and Sk % kc == 0:
        return flash_attention_jnp(q, k, v, causal, qc, kc)
    return chunked_attention(q, k, v, causal=causal, q_offset=q_offset,
                             kv_len=kv_len, q_chunk=qc, k_chunk=kc)


def attention(
    params: Dict,
    x: jnp.ndarray,                      # (B, S, d)
    cfg: ModelConfig,
    positions: Optional[jnp.ndarray],    # (B,S) or (3,B,S) for mrope
    *,
    causal: bool = True,
    kv_input: Optional[jnp.ndarray] = None,   # cross-attention memory (B,Sk,d)
    cache: Optional[Dict] = None,
    cache_index: Optional[jnp.ndarray] = None,  # write offset: scalar or (B,)
    cache_layer: Optional[jnp.ndarray] = None,  # layer of a stacked cache
    rope_cache=None,
    kv_len: Optional[jnp.ndarray] = None,  # positions read; cache_index + S
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Self- or cross-attention with optional KV cache.

    Modes:
      * train: ``cache=None``, full-sequence causal.
      * cached: ``cache`` (lane-dense, `init_kv_cache`) + ``cache_index``
        given: write the S new k/v rows at ``cache_index`` and attend over
        the valid prefix (S == 1 decode, or S > 1 prefill-into-cache).
      * cross: ``kv_input`` given (no cache, no causality).
    """
    cd = dtype_of(cfg.compute_dtype)
    B, S, _ = x.shape
    kv_src = x if kv_input is None else kv_input
    q = _split_heads(apply_linear(params["wq"], x, cd), cfg.n_heads, cfg.d_head)
    k = _split_heads(apply_linear(params["wk"], kv_src, cd), cfg.n_kv_heads, cfg.d_head)
    v = _split_heads(apply_linear(params["wv"], kv_src, cd), cfg.n_kv_heads, cfg.d_head)
    q = constrain(q, ("dp", None, "tp", None))
    k = constrain(k, ("dp", None, "tp", None))
    v = constrain(v, ("dp", None, "tp", None))

    if kv_input is None:  # RoPE only applies to self-attention
        q = _rope(cfg, q, positions, rope_cache)
        k = _rope(cfg, k, positions, rope_cache)

    new_cache = None
    if cache is not None:
        # Write this step's k/v rows at the write offset, then attend over
        # the valid prefix.  ``cache_layer`` given, the leaves are the whole
        # stack of layers and are written and read at that layer in place.
        with jax.named_scope(KV_SCOPE):
            k_cache = write_kv_rows(cache["k"], k.reshape(B, S, -1),
                                    cache_index, cache_layer)
            v_cache = write_kv_rows(cache["v"], v.reshape(B, S, -1),
                                    cache_index, cache_layer)
        new_cache = {"k": k_cache, "v": v_cache}
        if kv_len is None:
            kv_len = cache_index + S
        if S == 1:
            from repro.kernels import ops as kops
            with jax.named_scope(ATTN_SCOPE):
                if cache_layer is None:  # one layer's leaves: a stack of one
                    k_cache, v_cache, cache_layer = (k_cache[None],
                                                     v_cache[None], 0)
                out = kops.stacked_decode_attention(
                    q, k_cache, v_cache, cache_layer, kv_len)
        else:
            # Prefill-into-cache: causal with absolute offset.
            out = _self_attention_math(
                q, kv_heads(k_cache, cache_layer, cfg.d_head),
                kv_heads(v_cache, cache_layer, cfg.d_head), causal=True,
                q_offset=cache_index, kv_len=kv_len)
    else:
        out = _self_attention_math(q, k, v, causal=causal and kv_input is None)

    out = constrain(out, ("dp", None, "tp", None))
    out = out.reshape(B, S, cfg.n_heads * cfg.d_head)
    return apply_linear(params["wo"], out, cd), new_cache

