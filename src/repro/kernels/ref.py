"""Pure-jnp oracles for every Pallas kernel.

These delegate to the model-layer reference implementations (single source
of truth — the same code the smoke tests and the lowered dry-run programs
use), re-exported under kernel-oriented names for the per-kernel allclose
sweeps in tests/test_kernels.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import gqa_reference, kv_heads
from repro.models.layers import rms_norm as _rms_norm_model
from repro.models.ssm import ssd_chunked, ssd_reference


def flash_attention_ref(q, k, v, causal: bool = True) -> jax.Array:
    """(B,Sq,Hq,D) GQA attention, fp32 softmax."""
    return gqa_reference(q, k, v, causal=causal)


def decode_attention_ref(q, k_cache, v_cache, kv_len) -> jax.Array:
    """One-token decode against a (B,Sk,Hkv,D) cache with valid prefix."""
    return gqa_reference(q, k_cache, v_cache, causal=False, kv_len=kv_len)


def stacked_decode_attention_ref(q, k_stack, v_stack, layer, kv_len):
    """One-token decode against layer ``layer`` of a (L,B,Sk,Hkv·D) stack."""
    D = q.shape[-1]
    return gqa_reference(q, kv_heads(k_stack, layer, D),
                         kv_heads(v_stack, layer, D), causal=False,
                         kv_len=kv_len)


def rms_norm_ref(x, scale, eps: float = 1e-5) -> jax.Array:
    return _rms_norm_model(x, scale, eps)


def ssm_scan_ref(x, Bm, Cm, dt, A_log, D, chunk: int = 64):
    """Chunked SSD (itself validated against the sequential `ssd_reference`)."""
    return ssd_chunked(x, Bm, Cm, dt, A_log, D, chunk)


def ssm_scan_sequential_ref(x, Bm, Cm, dt, A_log, D):
    return ssd_reference(x, Bm, Cm, dt, A_log, D)
