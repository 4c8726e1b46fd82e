"""Pallas TPU decode attention: one new token per sequence against a long
KV cache (decode_32k / long_500k serve cells).

Grid = (B·Hkv, Sk/block_k); per program, the G grouped q-heads of one kv
head attend to one KV block with (m, l, acc) scratch carried across the
sequential k dimension.  The valid prefix length of every row arrives as a
scalar-prefetch operand (SMEM, whole array); everything past it is masked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float, block_k: int, n_k: int):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[pl.program_id(0)]
    run = kj * block_k < kv_len  # skip fully-invalid blocks

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)          # (G, D)
        k = k_ref[...].astype(jnp.float32)          # (block_k, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G,bk)
        kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(
    q: jax.Array,          # (B, 1, Hq, D)
    k_cache: jax.Array,    # (B, Sk, Hkv, D)
    v_cache: jax.Array,
    kv_len: jax.Array,     # scalar or (B,) int32 — valid prefix length
    block_k: int = 512,
    interpret: bool = True,
) -> jax.Array:
    B, _, Hq, D = q.shape
    Sk, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    block_k = min(block_k, Sk)
    if Sk % block_k:
        raise ValueError(f"cache len {Sk} % block_k {block_k} != 0")
    n_k = Sk // block_k
    qf = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    lens = jnp.repeat(lens, Hkv)  # (B*Hkv,)

    kernel = functools.partial(_kernel, scale=D ** -0.5, block_k=block_k, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * Hkv, n_k),
            in_specs=[
                pl.BlockSpec((None, G, D), lambda h, j, lens: (h, 0, 0)),
                pl.BlockSpec((None, block_k, D), lambda h, j, lens: (h, j, 0)),
                pl.BlockSpec((None, block_k, D), lambda h, j, lens: (h, j, 0)),
            ],
            out_specs=pl.BlockSpec((None, G, D), lambda h, j, lens: (h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(B, 1, Hq, D)
