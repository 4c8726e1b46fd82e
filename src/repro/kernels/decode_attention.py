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


# ------------------------------------------------- stacked, lane-dense cache
def _lane_tile(d_head: int, width: int) -> int:
    """Lanes of K/V one MXU pass takes: 128 when heads pack into 128-lane
    tiles, one head when it fills whole tiles, else the whole row."""
    if d_head % 128 == 0:
        return d_head
    if 128 % d_head == 0 and width % 128 == 0:
        return 128
    return width


def _stacked_kernel(layer_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, scale: float, tile: int,
                    n_tiles: int, block_k: int, n_k: int):
    del layer_ref  # consumed by the index maps
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[pl.program_id(0)]
    for t in range(n_tiles):
        lanes = slice(t * tile, (t + 1) * tile)
        k = k_ref[:, lanes]                                   # (block_k, tile)
        s = jax.lax.dot_general(q_ref[t], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)              # (rows, block_k)
        m_prev = m_ref[t]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[t] = l_ref[t] * corr + p.sum(axis=-1, keepdims=True)
        v = v_ref[:, lanes]
        acc_ref[t] = acc_ref[t] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[t] = m_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_bytes", "interpret"))
def stacked_decode_attention(
    q: jax.Array,          # (B, 1, Hq, D)
    k_stack: jax.Array,    # (L, B, Sk, Hkv·D): every layer's keys, lane-dense
    v_stack: jax.Array,
    layer: jax.Array,      # scalar int32: the layer to read
    kv_len: jax.Array,     # scalar or (B,) int32: valid prefix length
    block_bytes: int = 2 << 20,
    interpret: bool = True,
) -> jax.Array:
    """One new token per sequence against layer ``layer`` of a stacked,
    lane-dense KV cache, read where it lies: the layer index reaches the
    K/V index maps by scalar prefetch, so no layer is sliced out.

    Grid = (B, Sk/block_k).  A program takes ``(block_k, Hkv·D)`` rows of
    one sequence's keys and values and walks their 128-lane tiles.  The
    heads that share a tile (two of 64 lanes) are one pass: their queries
    go in as a block-diagonal ``(heads·G, 128)`` operand, zero outside each
    head's lanes, so the scores are exact, and the matching diagonal
    blocks of ``p @ V`` are kept.  Scores and softmax are f32; ``p`` meets
    ``V`` in the cache's dtype with f32 accumulation; every position past
    ``kv_len`` is masked."""
    B, _, Hq, D = q.shape
    _, _, Sk, W = k_stack.shape
    Hkv = W // D
    G = Hq // Hkv
    tile = _lane_tile(D, W)
    n_tiles, per = W // tile, tile // D         # tiles, heads per tile
    rows = per * G
    block_k = Sk
    while (block_k * W * k_stack.dtype.itemsize > block_bytes
           and block_k % 32 == 0):
        block_k //= 2
    n_k = Sk // block_k

    # (B, tiles, heads in tile, G, D) -> block-diagonal (B, tiles, rows, tile).
    eye = jnp.eye(per, dtype=q.dtype)
    qt = q.reshape(B, n_tiles, per, G, D)
    q_bd = (qt[:, :, :, :, None, :] * eye[:, None, :, None]).reshape(
        B, n_tiles, rows, tile)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    kv_spec = pl.BlockSpec((None, None, block_k, W),
                           lambda b, j, layer, lens: (layer[0], b, j, 0))
    tiles_spec = pl.BlockSpec((None, n_tiles, rows, tile),
                              lambda b, j, layer, lens: (b, 0, 0, 0))
    kernel = functools.partial(
        _stacked_kernel, scale=D ** -0.5, tile=tile, n_tiles=n_tiles,
        block_k=block_k, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_k),
            in_specs=[tiles_spec, kv_spec, kv_spec],
            out_specs=tiles_spec,
            scratch_shapes=[
                pltpu.VMEM((n_tiles, rows, 1), jnp.float32),
                pltpu.VMEM((n_tiles, rows, 1), jnp.float32),
                pltpu.VMEM((n_tiles, rows, tile), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_tiles, rows, tile), q.dtype),
        interpret=interpret,
    )(layer, lens, q_bd, k_stack, v_stack)
    # Keep each head's own lanes: the diagonal blocks.
    out = out.reshape(B, n_tiles, per, G, per, D)
    out = jnp.diagonal(out, axis1=2, axis2=4)              # (B, t, G, D, per)
    return jnp.moveaxis(out, -1, 2).reshape(B, 1, Hq, D)
