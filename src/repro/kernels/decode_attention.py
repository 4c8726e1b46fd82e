"""Pallas TPU decode attention: one new token per sequence against one
layer of a stacked, lane-dense KV cache ``(L, B, Sk, Hkv·D)``.

The cache stays in HBM.  One program walks the slots that hold a
sequence and copies only the ``kv_block`` blocks of each live prefix
into two VMEM buffers, the next copy in flight while the current block
is computed; a slot of length 0 copies nothing and reads zeros.  The
layer and the lengths arrive as scalar-prefetch operands (SMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: Bytes of keys (and as many of values) one copy of the stacked kernel
#: brings into VMEM.
KV_BLOCK_BYTES = 512 << 10


def kv_block(max_len: int, row_bytes: int,
             block_bytes: int = KV_BLOCK_BYTES) -> int:
    """Positions the stacked kernel copies at a time from a cache of
    ``max_len`` positions of ``row_bytes`` each: ``max_len`` halved while
    its rows exceed ``block_bytes`` and it stays a multiple of 16."""
    block = max_len
    while block * row_bytes > block_bytes and block % 32 == 0:
        block //= 2
    return block


def _stacked_kernel(layer_ref, len_ref, q_ref, place_ref, spread_ref, k_hbm,
                    v_hbm, o_ref, k_buf, v_buf, sems, first_ref, next_ref,
                    m_ref, l_ref, acc_ref, *, scale: float, block_k: int):
    """Every slot that holds a sequence, in turn: its live blocks, copied
    into two VMEM buffers; the other slots' outputs are zeros.

    The live blocks of all slots form one sequence, block ``g`` in buffer
    ``g % 2``: each step starts the copy of the next live block, the first
    of the next live slot included, before it waits for its own, so a copy
    is in flight while the previous block is computed."""
    n_slots, max_len = len_ref.shape[0], k_hbm.shape[2]
    # Moving values by products with 0s and 1s is exact in one bf16 pass;
    # f32 takes the multi-pass product.
    exact = (jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
             else None)

    def length(b):
        return jnp.clip(len_ref[b], 0, max_len)

    # first[b]: slot b's first block in the sequence (first[n_slots]: the
    # count); next[b]: the first slot at or after b that has a block.
    first_ref[0] = 0
    next_ref[n_slots] = n_slots

    def count(b, carry):
        first_ref[b + 1] = first_ref[b] + (length(b) + block_k - 1) // block_k
        back = n_slots - 1 - b
        next_ref[back] = jnp.where(length(back) > 0, back, next_ref[back + 1])
        return carry

    jax.lax.fori_loop(0, n_slots, count, 0)
    total = first_ref[n_slots]

    def copies(slot, j, buf):
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        return [pltpu.make_async_copy(src.at[layer_ref[0], slot, rows],
                                      dst.at[buf], sems.at[i, buf])
                for i, (src, dst) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    @pl.when(total > 0)
    def _first():
        for c in copies(next_ref[0], 0, 0):
            c.start()

    o_ref[...] = jnp.zeros_like(o_ref)
    place = place_ref[...]                                    # (Hq, W)

    def attend(b):
        kv_len, first = length(b), first_ref[b]
        n_blocks = first_ref[b + 1] - first
        # Each query head spread over its KV head's lanes, zero elsewhere.
        q = jax.lax.dot_general(q_ref[b], spread_ref[...],
                                (((1,), (0,)), ((), ())), precision=exact,
                                preferred_element_type=jnp.float32)
        q = (q * place).astype(k_buf.dtype)                   # (Hq, W)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def block(j, carry):
            g = first + j
            buf = g % 2

            @pl.when(g + 1 < total)
            def _prefetch():
                last = j + 1 == n_blocks
                for c in copies(jnp.where(last, next_ref[b + 1], b),
                                jnp.where(last, 0, j + 1), 1 - buf):
                    c.start()

            for c in copies(b, j, buf):
                c.wait()
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            s = jax.lax.dot_general(q, k_buf[buf], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(kpos.T < kv_len, s * scale, NEG_INF)  # (Hq, block_k)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
            v = jnp.where(kpos < kv_len, v_buf[buf], 0)
            acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)
        # Each head's own lanes, gathered back to (Hq, D).
        out = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30) * place
               ).astype(o_ref.dtype)
        o_ref[b] = jax.lax.dot_general(
            out, spread_ref[...], (((1,), (1,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)
        return next_ref[b + 1]

    jax.lax.while_loop(lambda b: b < n_slots, attend, next_ref[0])


@functools.partial(jax.jit, static_argnames=("block_bytes", "interpret"))
def stacked_decode_attention(
    q: jax.Array,          # (B, 1, Hq, D)
    k_stack: jax.Array,    # (L, B, Sk, Hkv·D): every layer's keys, lane-dense
    v_stack: jax.Array,
    layer: jax.Array,      # scalar int32: the layer to read
    kv_len: jax.Array,     # scalar or (B,) int32: valid prefix length
    block_bytes: int = KV_BLOCK_BYTES,
    interpret: bool = True,
) -> jax.Array:
    """One new token per sequence against layer ``layer`` of a stacked,
    lane-dense KV cache, read where it lies and only as far as each
    sequence's ``kv_len``: the cache stays in HBM, and the kernel copies
    the ``kv_block`` blocks that hold each live prefix into VMEM.  A
    sequence of length 0 copies nothing and reads zeros.

    One program walks the slots.  Each block is one pass over whole rows:
    the queries go in block-diagonal, ``(Hq, Hkv·D)`` with each head's
    ``D`` values in its KV head's lanes and zero elsewhere, so the scores
    are exact, and each head keeps its own lanes of ``p @ V``.  Scores and
    softmax are f32; ``p`` meets ``V`` in the cache's dtype with f32
    accumulation; every position past ``kv_len`` in a block read is
    masked."""
    B, _, Hq, D = q.shape
    _, _, Sk, W = k_stack.shape
    G = Hq // (W // D)
    block_k = kv_block(Sk, W * k_stack.dtype.itemsize, block_bytes)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    lane = np.arange(W)
    # Constants: spread (D, W), D's lanes repeated in every KV head's;
    # place (Hq, W), each query head's KV head's lanes.
    spread = jnp.asarray(lane[None] % D == np.arange(D)[:, None], q.dtype)
    place = jnp.asarray(lane[None] // D == np.arange(Hq)[:, None] // G,
                        jnp.float32)

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(_stacked_kernel, scale=D ** -0.5,
                               block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole((B, Hq, D)), whole((Hq, W)), whole((D, W)),
                      hbm, hbm],
            out_specs=whole((B, Hq, D)),
            scratch_shapes=[
                pltpu.VMEM((2, block_k, W), k_stack.dtype),
                pltpu.VMEM((2, block_k, W), v_stack.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((B + 1,), jnp.int32),
                pltpu.SMEM((B + 1,), jnp.int32),
                pltpu.VMEM((Hq, 1), jnp.float32),
                pltpu.VMEM((Hq, 1), jnp.float32),
                pltpu.VMEM((Hq, W), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(layer, lens, q.reshape(B, Hq, D), place, spread, k_stack, v_stack)
    return out.reshape(B, 1, Hq, D)
