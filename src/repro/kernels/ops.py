"""Jit'd kernel entry points with backend dispatch.

On TPU the Pallas kernels compile natively (``interpret=False``).  On the
CPU backend (the test suite, ``JAX_PLATFORMS=cpu``) they run in interpret
mode, which executes the kernel body op-by-op — the same program
structure, so correctness tests on CPU validate the TPU kernel logic.  Any
other backend is refused rather than silently interpreted.  Model code
(`cfg.attn_impl`/`cfg.ssm_impl`) routes here when the kernels are enabled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import decode_attention as _decode
from . import flash_attention as _flash
from . import ref
from . import rmsnorm as _rmsnorm
from . import ssm_scan as _ssm


@functools.cache
def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run natively on TPU or interpreted on "
                       f"CPU; backend {backend!r} has neither path")


def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    return _flash.flash_attention(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=_interpret())


def decode_attention(q, k_cache, v_cache, kv_len, block_k: int = 512):
    return _decode.decode_attention(q, k_cache, v_cache, kv_len,
                                    block_k=block_k, interpret=_interpret())


def stacked_decode_attention(q, k_stack, v_stack, layer, kv_len):
    """The default decode attention over a stacked, lane-dense cache.
    Lowered for the TPU it is the Pallas kernel, which reads the layer
    where it lies; for any other platform the XLA-native oracle, so that
    CPU runs and the dry-run lower plain XLA ops (tests/test_kernels.py
    checks the kernel, interpreted, against that oracle)."""
    return jax.lax.platform_dependent(
        q, k_stack, v_stack, jnp.asarray(layer, jnp.int32),
        jnp.asarray(kv_len, jnp.int32),
        tpu=functools.partial(_decode.stacked_decode_attention,
                              interpret=False),
        default=ref.stacked_decode_attention_ref)


def rms_norm(x, scale, eps: float = 1e-5, block_rows: int = 256):
    return _rmsnorm.rms_norm(x, scale, eps=eps, block_rows=block_rows,
                             interpret=_interpret())


def ssm_scan(x, Bm, Cm, dt, A_log, D, chunk: int = 64):
    return _ssm.ssm_scan(x, Bm, Cm, dt, A_log, D, chunk=chunk,
                         interpret=_interpret())
