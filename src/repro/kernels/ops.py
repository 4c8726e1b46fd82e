"""Jit'd kernel entry points with backend dispatch.

On TPU the Pallas kernels compile natively (``interpret=False``).  On the
CPU backend (the test suite, ``JAX_PLATFORMS=cpu``) they run in interpret
mode, which executes the kernel body op-by-op — the same program
structure, so correctness tests on CPU validate the TPU kernel logic.  Any
other backend is refused rather than silently interpreted.  The model's
cached decode attention always calls in here; its SSM layers do when
``cfg.ssm_impl`` is ``"pallas"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import decode_attention as _decode
from . import ref
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


def ssm_scan(x, Bm, Cm, dt, A_log, D, chunk: int = 64):
    return _ssm.ssm_scan(x, Bm, Cm, dt, A_log, D, chunk=chunk,
                         interpret=_interpret())
