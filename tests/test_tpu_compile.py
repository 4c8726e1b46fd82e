"""Compile the main-path Pallas kernels for a described TPU v5e (no chip
needed): what interpret mode cannot show — block shapes the TPU lowering
refuses, unsupported primitives, VMEM overruns — fails here.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under several test workers the others
must still collect the same tests (they skip here instead)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention as _decode
from repro.kernels import flash_attention as _flash
from repro.kernels import rmsnorm as _rmsnorm
from repro.kernels import ssm_scan as _ssm

QWEN = get_config("qwen1.5-0.5b")      # 16 heads (kv 16) of 64, d_model 1024
ZAMBA = get_config("zamba2-7b")        # Mamba2: d_inner 7168 = 112 heads of 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.mark.parametrize("B,Sk,Hq,Hkv,D", [
    (16, 4096, QWEN.n_heads, QWEN.n_kv_heads, QWEN.d_head),   # qwen decode
    (8, 4096, 32, 4, 128),                                     # GQA, G = 8
])
def test_decode_attention_compiles(one_chip, B, Sk, Hq, Hkv, D):
    hlo = _compile(
        lambda q, k, v, n: _decode.decode_attention(q, k, v, n, interpret=False),
        [((B, 1, Hq, D), BF16), ((B, Sk, Hkv, D), BF16),
         ((B, Sk, Hkv, D), BF16), ((B,), I32)], one_chip)
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    qkv = ((1, 2048, QWEN.n_heads, QWEN.d_head), BF16)
    hlo = _compile(
        lambda q, k, v: _flash.flash_attention(q, k, v, interpret=False),
        [qkv, qkv, qkv], one_chip)
    assert "tpu_custom_call" in hlo


def test_rms_norm_compiles(one_chip):
    hlo = _compile(lambda x, s: _rmsnorm.rms_norm(x, s, interpret=False),
                   [((4096, QWEN.d_model), BF16), ((QWEN.d_model,), BF16)],
                   one_chip)
    assert "tpu_custom_call" in hlo


def test_ssm_scan_compiles(one_chip):
    H = ZAMBA.ssm_expand * ZAMBA.d_model // ZAMBA.mamba_headdim
    P, N, S = ZAMBA.mamba_headdim, ZAMBA.ssm_state, 2048
    hlo = _compile(
        lambda x, b, c, dt, a, d: _ssm.ssm_scan(x, b, c, dt, a, d,
                                                chunk=ZAMBA.ssm_chunk,
                                                interpret=False),
        [((1, S, H, P), BF16), ((1, S, N), BF16), ((1, S, N), BF16),
         ((1, S, H), F32), ((H,), F32), ((H,), F32)], one_chip)
    assert "tpu_custom_call" in hlo
