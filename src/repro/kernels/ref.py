"""Pure-jnp oracles for both Pallas kernels.

These delegate to the model-layer reference implementations (single source
of truth — the same code the smoke tests and the lowered dry-run programs
use), re-exported under kernel-oriented names for the per-kernel allclose
sweeps in tests/test_kernels.py.
"""

from __future__ import annotations

from repro.models.attention import gqa_reference, kv_heads
from repro.models.ssm import ssd_chunked, ssd_reference


def stacked_decode_attention_ref(q, k_stack, v_stack, layer, kv_len):
    """One-token decode against layer ``layer`` of a (L,B,Sk,Hkv·D) stack."""
    D = q.shape[-1]
    return gqa_reference(q, kv_heads(k_stack, layer, D),
                         kv_heads(v_stack, layer, D), causal=False,
                         kv_len=kv_len)


def ssm_scan_ref(x, Bm, Cm, dt, A_log, D, chunk: int = 64):
    """Chunked SSD (itself validated against the sequential `ssd_reference`)."""
    return ssd_chunked(x, Bm, Cm, dt, A_log, D, chunk)


def ssm_scan_sequential_ref(x, Bm, Cm, dt, A_log, D):
    return ssd_reference(x, Bm, Cm, dt, A_log, D)
