"""Pallas TPU Mamba2 SSD chunked scan.

Grid = (B·H, S/chunk) with the innermost (chunk) dim sequential; the (P, N)
state lives in VMEM scratch across chunks, so HBM sees each input exactly
once and each output exactly once — the jnp reference materializes
(B, nc, L, L, H) decay tensors instead (the memory-term gap the §Perf log
quantifies).

Per program: x (L, P), B/C (L, N), dt and the in-chunk cumulative log
decay (L, 1) for one (batch, head, chunk): intra-chunk quadratic form +
state update, all in fp32 in VMEM.  The cumulative decay is summed outside
the kernel (Mosaic has no cumsum), with the reference's own expression.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(d_ref, x_ref, b_ref, c_ref, dt_ref, cum_ref, y_ref, s_out_ref,
            state_ref, *, chunk: int, n_chunks: int):
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    D = d_ref[pl.program_id(0)]                     # scalar (SMEM)
    x = x_ref[...].astype(jnp.float32)              # (L, P)
    Bm = b_ref[...].astype(jnp.float32)             # (L, N)
    Cm = c_ref[...].astype(jnp.float32)             # (L, N)
    dt = dt_ref[...].astype(jnp.float32)            # (L, 1)
    cum = cum_ref[...]                              # (L, 1) inclusive log decay

    # Intra-chunk weights w[i,j] = exp(cum_i − cum_j)·dt_j, j ≤ i.
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    w = jnp.where(ii >= jj, jnp.exp(cum - cum.T) * dt.T, 0.0)
    g = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # (L, L) C_i·B_j
    y_intra = jax.lax.dot_general(g * w, x, (((1,), (0,)), ((), ())))

    # Inter-chunk from carried state: y_i += exp(cum_i)·C_i·S.
    S = state_ref[...]                              # (P, N)
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        Cm, S, (((1,), (1,)), ((), ())))            # (L, P)
    y_ref[...] = (y_intra + y_inter + D * x).astype(y_ref.dtype)

    # State update: S ← exp(cum_L)·S + Σ_j exp(cum_L − cum_j)·dt_j·x_j⊗B_j.
    # cum_L as a scalar: Mosaic cannot broadcast a (1, 1) vector to (P, N).
    cum_last = jnp.sum(cum[chunk - 1:, :])
    wL = jnp.exp(cum_last - cum) * dt               # (L, 1)
    state_ref[...] = jnp.exp(cum_last) * S + jax.lax.dot_general(
        x * wL, Bm, (((0,), (0,)), ((), ())))

    @pl.when(cj == n_chunks - 1)
    def _emit_state():
        s_out_ref[...] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(
    x: jax.Array,        # (B, S, H, P)
    Bm: jax.Array,       # (B, S, N)
    Cm: jax.Array,       # (B, S, N)
    dt: jax.Array,       # (B, S, H) post-softplus
    A_log: jax.Array,    # (H,)
    D: jax.Array,        # (H,)
    chunk: int = 64,
    interpret: bool = True,
):
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    if S % chunk:
        raise ValueError(f"S {S} % chunk {chunk} != 0")
    nc = S // chunk
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, S, 1)
    dtc = dt.reshape(B, nc, chunk, H).astype(jnp.float32)
    cum = jnp.cumsum(-jnp.exp(A_log.astype(jnp.float32)) * dtc, axis=2)
    cumf = cum.reshape(B, S, H).transpose(0, 2, 1).reshape(B * H, S, 1)
    Df = jnp.tile(D.astype(jnp.float32), B)                           # (B*H,)

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=nc)
    y, s_final = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,   # D: one scalar per (batch, head) row
            grid=(B * H, nc),
            in_specs=[
                pl.BlockSpec((None, chunk, P), lambda g, c, d: (g, c, 0)),
                pl.BlockSpec((None, chunk, N), lambda g, c, d: (g // H, c, 0)),
                pl.BlockSpec((None, chunk, N), lambda g, c, d: (g // H, c, 0)),
                pl.BlockSpec((None, chunk, 1), lambda g, c, d: (g, c, 0)),
                pl.BlockSpec((None, chunk, 1), lambda g, c, d: (g, c, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, chunk, P), lambda g, c, d: (g, c, 0)),
                pl.BlockSpec((None, P, N), lambda g, c, d: (g, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(Df, xf, Bm, Cm, dtf, cumf)
    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    state = s_final.reshape(B, H, P, N)
    return y, state
