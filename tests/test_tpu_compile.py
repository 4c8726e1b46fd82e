"""Compile the main-path Pallas kernels for a described TPU v5e (no chip
needed): what interpret mode cannot show — block shapes the TPU lowering
refuses, unsupported primitives, VMEM overruns — fails here.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under several test workers the others
must still collect the same tests (they skip here instead)."""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention as _decode
from repro.kernels import ssm_scan as _ssm
from repro.models import init_cache, init_lm
from repro.serve.engine import make_serve_step

QWEN = get_config("qwen1.5-0.5b")      # 16 heads (kv 16) of 64, d_model 1024
GRANITE = get_config("granite-3-2b")   # 32 heads (kv 8) of 64, d_model 2048
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


@pytest.mark.parametrize("L,B,Sk,Hq,Hkv,D", [
    (40, 32, 2048, 32, 8, 64),      # granite: two heads per 128-lane tile
    (24, 32, 2048, 16, 16, 64),     # qwen, MHA
    (4, 8, 4096, 32, 4, 128),       # one head per tile, two key blocks
    (1, 16, 4096, 16, 16, 64),      # one layer, qwen's heads, 4096 positions
    (1, 8, 4096, 32, 4, 128),       # one layer, GQA with G = 8
])
def test_stacked_decode_attention_compiles(one_chip, L, B, Sk, Hq, Hkv, D):
    hlo = _compile(
        lambda q, k, v, layer, n: _decode.stacked_decode_attention(
            q, k, v, layer, n, interpret=False),
        [((B, 1, Hq, D), BF16), ((L, B, Sk, Hkv * D), BF16),
         ((L, B, Sk, Hkv * D), BF16), ((), I32), ((B,), I32)], one_chip)
    assert "tpu_custom_call" in hlo


_HLO_DEF = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+?)(?:\{[^}]*\})? (\S+?)\((.*)$")


def _elements(shape: str) -> int:
    m = re.match(r"^\w+\[([\d,]*)\]", shape)
    return math.prod(int(d) for d in m.group(1).split(",") if d) if m else 0


def _cache_sized_moves(hlo: str, layer: int):
    """Instructions of a compiled program that move ``layer`` elements or
    more: a ``copy`` or ``dynamic-slice`` result that large (fusions count
    by the name XLA gives them after their root), or a
    ``dynamic-update-slice`` that writes that much.  A dynamic-update-slice
    whose update is small is in place on its buffer and passes."""
    size, defs = {}, []
    for line in hlo.splitlines():
        m = _HLO_DEF.match(line)
        if m:
            name, shape, op, rest = m.groups()
            size[name] = _elements(shape)
            defs.append((name, op if op != "fusion" else name, rest))
    bad = []
    for name, kind, rest in defs:
        operands = re.findall(r"%([\w.\-]+)", rest.split(")")[0])
        if "dynamic-update-slice" in kind:
            if any(size.get(o, 0) >= layer for o in operands[1:]):
                bad.append(name)
        elif kind.startswith("copy") and kind not in ("copy-start", "copy-done"):
            if size[name] >= layer:
                bad.append(name)
        elif "dynamic-slice" in kind and size[name] >= layer:
            bad.append(name)
    return bad


@pytest.mark.parametrize("cfg", [GRANITE, QWEN],
                         ids=["granite2b.chat", "qwen05b.move4"])
def test_decode_step_keeps_the_stacked_cache_in_place(one_chip, cfg):
    """The engine's step (`make_serve_step`: the continuous-batching decode
    step with its tokens chosen and sampled on the device) at both cells'
    shapes (published widths and depth, 32 slots x 2048), with the
    live-prefix kernel: each
    layer writes only its new rows into the donated stacked cache, which
    stays in place (aliased to the output), and reads its layer where it
    lies.  No instruction copies, slices or rewrites a layer's K or V
    (B x max_len x Hkv*Dh), and the temporaries stay below one layer's K
    (64 MiB on granite).  A cache stored (..., Hkv, 64) is kept
    position-minor and relaid twice a layer: 404 MB of temporaries on
    granite."""
    B, T = 32, 2048

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    params = shaped(jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg)))
    cache = shaped(jax.eval_shape(
        lambda: init_cache(cfg, B, T, per_slot_index=True)))
    feed = jax.ShapeDtypeStruct((4, B), I32, sharding=one_chip)
    prev = jax.ShapeDtypeStruct((B,), I32, sharding=one_chip)
    compiled = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, cache, feed, prev).compile()
    hlo = compiled.as_text()
    layer = B * T * cfg.n_kv_heads * cfg.d_head
    assert _cache_sized_moves(hlo, layer) == []
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert memory.temp_size_in_bytes < layer * 2    # one layer's K, bf16
    assert "stacked_decode_attention" in hlo and "tpu_custom_call" in hlo


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
