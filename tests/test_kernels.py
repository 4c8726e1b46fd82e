"""Allclose sweeps across shapes and dtypes, plus hypothesis property tests
on invariants: the Pallas kernels (interpret mode on CPU) against their jnp
oracles, and the model's XLA attention and norm against references."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import decode_attention as _decode
from repro.kernels import ops, ref
from repro.models.attention import (chunked_attention, flash_attention_jnp,
                                    gqa_reference)
from repro.models.layers import rms_norm

KEY = jax.random.PRNGKey(0)
EPS = 1e-5
_flash = jax.jit(flash_attention_jnp, static_argnums=(3, 4, 5))


def _rand(shape, dtype, k):
    x = jax.random.normal(jax.random.fold_in(KEY, k), shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


class TestFlashAttention:
    """The XLA online-softmax attention that training and long prefill run,
    at query/key chunks of ``bq``/``bk``: `flash_attention_jnp` (causal, as
    training calls it) and `chunked_attention` (non-causal), against
    `gqa_reference`."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,S,Hq,Hkv,D,bq,bk", [
        (1, 128, 4, 4, 64, 64, 64),     # MHA
        (2, 256, 8, 2, 64, 128, 64),    # GQA 4:1
        (2, 256, 6, 3, 32, 64, 128),    # odd head count
        (1, 512, 4, 1, 128, 128, 128),  # MQA, MXU-aligned
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, B, S, Hq, Hkv, D, bq, bk, causal, dtype):
        q = _rand((B, S, Hq, D), dtype, 1)
        k = _rand((B, S, Hkv, D), dtype, 2)
        v = _rand((B, S, Hkv, D), dtype, 3)
        if causal:
            out = _flash(q, k, v, True, bq, bk)
        else:
            out = chunked_attention(q, k, v, causal=False, q_chunk=bq,
                                    k_chunk=bk)
        want = gqa_reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), **_tol(dtype))

    def test_block_size_invariance(self):
        q = _rand((1, 256, 4, 64), jnp.float32, 4)
        k = _rand((1, 256, 2, 64), jnp.float32, 5)
        v = _rand((1, 256, 2, 64), jnp.float32, 6)
        outs = [np.asarray(_flash(q, k, v, True, bq, bk))
                for bq, bk in [(64, 64), (128, 64), (256, 128), (256, 256)]]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_gradients_match_ref(self, causal):
        """The hand-written flash backward (`_flash_bwd_rule`) gives the
        gradients of the reference for q, k and v."""
        q = _rand((2, 256, 8, 64), jnp.float32, 7)
        k = _rand((2, 256, 2, 64), jnp.float32, 8)
        v = _rand((2, 256, 2, 64), jnp.float32, 9)
        w = _rand((2, 256, 8, 64), jnp.float32, 10)

        def grads(attend):
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(attend(q, k, v) * w),
                argnums=(0, 1, 2)))(q, k, v)
        got = grads(lambda q, k, v: _flash(q, k, v, causal, 64, 128))
        want = grads(lambda q, k, v: gqa_reference(q, k, v, causal))
        for g, r in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       **_tol(jnp.float32))


class TestStackedDecodeAttention:
    """The decode kernel over a stacked, lane-dense (L, B, Sk, Hkv*D) cache,
    interpreted, against the oracle on the layer it names."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("L,B,Sk,Hq,Hkv,D,block_bytes", [
        (3, 4, 64, 8, 2, 64, 1 << 20),      # GQA 4:1, two heads per tile
        (2, 3, 128, 4, 4, 64, 1 << 14),     # MHA, four key blocks
        (2, 2, 64, 8, 1, 128, 1 << 20),     # MQA, one head per tile
        (2, 2, 32, 6, 3, 32, 1 << 20),      # heads of 32, not lane-aligned
        (1, 2, 64, 4, 1, 64, 1 << 20),      # one head narrower than a tile
        # Blocks of 64 / 128 / 96 positions in bf16 (half that in f32):
        (2, 1, 256, 4, 4, 64, 64 * 256 * 2),    # MHA
        (2, 2, 512, 8, 2, 64, 128 * 128 * 2),   # GQA 4:1
        (2, 3, 384, 6, 6, 32, 128 * 192 * 2),   # heads of 32, six KV heads
    ])
    def test_matches_ref(self, L, B, Sk, Hq, Hkv, D, block_bytes, dtype):
        q = _rand((B, 1, Hq, D), dtype, 20)
        k = _rand((L, B, Sk, Hkv * D), dtype, 21)
        v = _rand((L, B, Sk, Hkv * D), dtype, 22)
        kv_len = jnp.arange(1, B + 1, dtype=jnp.int32) * (Sk // (B + 1)) + 1
        for layer in range(L):
            out = _decode.stacked_decode_attention(
                q, k, v, jnp.int32(layer), kv_len, block_bytes=block_bytes,
                interpret=True)
            want = ref.stacked_decode_attention_ref(q, k, v, layer, kv_len)
            np.testing.assert_allclose(np.asarray(out, np.float32),
                                       np.asarray(want, np.float32),
                                       **_tol(dtype))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("Hq,Hkv", [(32, 8), (16, 16)],
                             ids=["gqa-512-lanes", "mha-1024-lanes"])
    def test_lengths_around_a_block_match_ref(self, Hq, Hkv, dtype):
        """Lengths 0, 1, block - 1, block, block + 1 and the whole cache in
        one batch: the kernel copies each slot's live blocks only, and a
        slot of length 0 reads zeros, as the oracle does."""
        D, Sk, block = 64, 64, 16
        W = Hkv * D
        q = _rand((6, 1, Hq, D), dtype, 29)
        k = _rand((2, 6, Sk, W), dtype, 30)
        v = _rand((2, 6, Sk, W), dtype, 31)
        kv_len = jnp.array([0, 1, block - 1, block, block + 1, Sk], jnp.int32)
        row = W * jnp.dtype(dtype).itemsize
        assert _decode.kv_block(Sk, row, block * row) == block
        out = _decode.stacked_decode_attention(
            q, k, v, jnp.int32(1), kv_len, block_bytes=block * row,
            interpret=True)
        want = ref.stacked_decode_attention_ref(q, k, v, 1, kv_len)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), **_tol(dtype))
        np.testing.assert_array_equal(np.asarray(out[0], np.float32), 0.0)
        np.testing.assert_array_equal(np.asarray(want[0], np.float32), 0.0)

    @pytest.mark.parametrize("Hq,Hkv", [(4, 2), (4, 4)], ids=["gqa", "mha"])
    def test_other_layers_and_stale_rows_are_not_read(self, Hq, Hkv):
        """Rows past each length, whole free slots and the other layers
        hold NaN: the output stays finite and the same."""
        q = _rand((3, 1, Hq, 64), jnp.float32, 23)
        k = _rand((3, 3, 128, Hkv * 64), jnp.float32, 24)
        v = _rand((3, 3, 128, Hkv * 64), jnp.float32, 25)
        lens = [40, 0, 97]
        kv_len = jnp.array(lens, jnp.int32)

        def run(k, v):
            return np.asarray(_decode.stacked_decode_attention(
                q, k, v, jnp.int32(1), kv_len, block_bytes=1 << 14,
                interpret=True))
        want = run(k, v)
        k, v = k.at[0].set(np.nan).at[2].set(np.nan), v.at[0].set(-999.0)
        v = v.at[2].set(np.nan)
        for b, n in enumerate(lens):
            k, v = k.at[1, b, n:].set(np.nan), v.at[1, b, n:].set(np.nan)
        got = run(k, v)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want)
        np.testing.assert_array_equal(got[1], 0.0)

    def test_ops_off_the_chip_is_the_oracle(self):
        q = _rand((2, 1, 8, 64), jnp.float32, 26)
        k = _rand((2, 2, 64, 128), jnp.float32, 27)
        v = _rand((2, 2, 64, 128), jnp.float32, 28)
        kv_len = jnp.array([5, 64], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(ops.stacked_decode_attention(q, k, v, 1, kv_len)),
            np.asarray(ref.stacked_decode_attention_ref(q, k, v, 1, kv_len)))


class TestRmsNorm:
    """`models.layers.rms_norm`, the norm every layer runs, against the
    formula in float64."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [(4, 128), (2, 37, 256), (1, 5, 7, 64), (300, 512)])
    def test_matches_ref(self, shape, dtype):
        x = _rand(shape, dtype, 13)
        scale = _rand((shape[-1],), dtype, 14)
        out = rms_norm(x, scale, EPS)
        x64 = np.asarray(x, np.float64)
        want = (x64 / np.sqrt(np.mean(x64 ** 2, axis=-1, keepdims=True) + EPS)
                * np.asarray(scale, np.float64))
        np.testing.assert_allclose(np.asarray(out, np.float32), want,
                                   **_tol(dtype))

    @given(rows=st.integers(1, 64), d=st.sampled_from([32, 64, 128]),
           seed=st.integers(0, 100))
    @settings(max_examples=15, deadline=None)
    def test_scale_property(self, rows, d, seed):
        """rms_norm(c·x) == rms_norm(x) for any c > 0 (scale invariance)."""
        x = jax.random.normal(jax.random.PRNGKey(seed), (rows, d))
        scale = jnp.ones((d,))
        a = np.asarray(rms_norm(x, scale, EPS))
        b = np.asarray(rms_norm(3.7 * x, scale, EPS))
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


class TestSsmScan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,S,H,P,N,chunk", [
        (1, 128, 2, 16, 8, 32),
        (2, 256, 4, 64, 16, 64),
        (2, 192, 3, 32, 64, 64),
    ])
    def test_matches_chunked_ref(self, B, S, H, P, N, chunk, dtype):
        x = _rand((B, S, H, P), dtype, 15)
        Bm = _rand((B, S, N), dtype, 16)
        Cm = _rand((B, S, N), dtype, 17)
        dt = jax.nn.softplus(_rand((B, S, H), jnp.float32, 18))
        A_log = _rand((H,), jnp.float32, 19) * 0.5
        D = _rand((H,), jnp.float32, 20)
        y, s = ops.ssm_scan(x, Bm, Cm, dt, A_log, D, chunk=chunk)
        yr, sr = ref.ssm_scan_ref(x, Bm, Cm, dt, A_log, D, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(yr, np.float32), **_tol(dtype))
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                                   atol=1e-3, rtol=1e-3)

    def test_chunked_ref_matches_sequential(self):
        """The chunked oracle itself is exact vs the step-by-step scan."""
        B, S, H, P, N = 2, 96, 3, 8, 4
        x = _rand((B, S, H, P), jnp.float32, 21)
        Bm = _rand((B, S, N), jnp.float32, 22)
        Cm = _rand((B, S, N), jnp.float32, 23)
        dt = jax.nn.softplus(_rand((B, S, H), jnp.float32, 24))
        A_log = _rand((H,), jnp.float32, 25) * 0.5
        D = _rand((H,), jnp.float32, 26)
        y1, s1 = ref.ssm_scan_ref(x, Bm, Cm, dt, A_log, D, chunk=16)
        y2, s2 = ref.ssm_scan_sequential_ref(x, Bm, Cm, dt, A_log, D)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)

    def test_decay_property(self):
        """With A → −∞-ish decay (large A·dt), state ≈ last-chunk-only: the
        output at position i must not depend on far-past inputs."""
        B, S, H, P, N = 1, 128, 1, 8, 4
        x = _rand((B, S, H, P), jnp.float32, 27)
        Bm = _rand((B, S, N), jnp.float32, 28)
        Cm = _rand((B, S, N), jnp.float32, 29)
        dt = jnp.full((B, S, H), 50.0)       # huge dt → decay ≈ 0
        A_log = jnp.zeros((H,))              # A = −1 → exp(−50) per step
        D = jnp.zeros((H,))
        y1, _ = ops.ssm_scan(x, Bm, Cm, dt, A_log, D, chunk=32)
        x2 = x.at[:, :64].set(123.0)         # perturb far past
        y2, _ = ops.ssm_scan(x2, Bm, Cm, dt, A_log, D, chunk=32)
        np.testing.assert_allclose(np.asarray(y1[:, -16:]),
                                   np.asarray(y2[:, -16:]), atol=1e-3)
