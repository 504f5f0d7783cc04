"""Allclose sweeps against the pure-jnp oracles in ``repro.kernels.ref``.

The int8 quantize pair runs as a Pallas kernel in interpret mode
(``interpret=True`` executes the kernel body, BlockSpec tiling included, on
the CPU; on a TPU the same kernel lowers through Mosaic). Attention, the
RG-LRU scan and WKV6 run as the models run them: the XLA code in
``repro.models`` (blocked attention, ``lru_scan``, ``chunked_wkv``), swept
over shapes, block sizes and dtypes against the sequential oracles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.models import attention, rglru, rwkv

# Jitted, as the models run them (eager dispatch of a scan is slow).
blocked_attention = jax.jit(
    attention.blocked_attention,
    static_argnames=("causal", "window", "q_block", "kv_block"),
)
flash_attention_xla = jax.jit(
    attention.flash_attention_xla, static_argnums=(3, 4, 5, 6)
)
lru_scan = jax.jit(rglru.lru_scan)
chunked_wkv = jax.jit(rwkv.chunked_wkv, static_argnames=("chunk",))
sequential_wkv = jax.jit(rwkv.sequential_wkv)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5
    )


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,s,hq,hkv,hd,qb,kb",
        [
            (1, 32, 4, 4, 16, 16, 16),   # MHA
            (2, 64, 8, 2, 32, 16, 16),   # GQA 4:1
            (1, 40, 8, 1, 64, 8, 16),    # MQA, ragged seq
            (2, 128, 4, 2, 16, 32, 64),  # kv_block > q_block
        ],
    )
    def test_causal(self, b, s, hq, hkv, hd, qb, kb, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, s, hq, hd)).astype(dtype)
        k = jax.random.normal(ks[1], (b, s, hkv, hd)).astype(dtype)
        v = jax.random.normal(ks[2], (b, s, hkv, hd)).astype(dtype)
        out = blocked_attention(q, k, v, causal=True, q_block=qb, kv_block=kb)
        r = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(r, np.float32), **_tol(dtype)
        )

    @pytest.mark.parametrize("window", [8, 24, 1000])
    def test_local_window(self, window):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (2, 64, 4, 16))
        k = jax.random.normal(ks[1], (2, 64, 2, 16))
        v = jax.random.normal(ks[2], (2, 64, 2, 16))
        out = blocked_attention(
            q, k, v, causal=True, window=window, q_block=16, kv_block=16
        )
        r = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(out, r, rtol=2e-5, atol=2e-5)

    def test_non_causal_cross(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 24, 4, 32))
        k = jax.random.normal(ks[1], (1, 56, 2, 32))  # Skv != Sq
        v = jax.random.normal(ks[2], (1, 56, 2, 32))
        out = blocked_attention(q, k, v, causal=False, q_block=8, kv_block=16)
        r = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(out, r, rtol=2e-5, atol=2e-5)

    def test_matches_xla_blocked_path(self):
        """The models' default attention (``flash_attention_xla``, with its
        own backward) and the blocked scan agree: the same algorithm."""
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (2, 48, 4, 16))
        k = jax.random.normal(ks[1], (2, 48, 2, 16))
        v = jax.random.normal(ks[2], (2, 48, 2, 16))
        a = flash_attention_xla(q, k, v, True, 0, 16, 16)
        b = blocked_attention(q, k, v, causal=True, q_block=16, kv_block=16)
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def _lru_inputs(key, shape, dtype=jnp.float32):
    ks = jax.random.split(key, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], shape)).astype(dtype)
    x = jax.random.normal(ks[1], shape).astype(dtype)
    return a, x


class TestLruScan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,s,w", [(1, 16, 8), (2, 40, 24), (2, 100, 32)])
    def test_vs_ref(self, b, s, w, dtype):
        a, x = _lru_inputs(jax.random.PRNGKey(0), (b, s, w), dtype)
        out = lru_scan(a, x)
        r = ref.lru_scan_ref(a, x)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(r, np.float32), **_tol(dtype)
        )

    @given(
        s=st.integers(2, 33),
        w=st.integers(1, 16),
    )
    @settings(max_examples=10, deadline=None)
    def test_property_random_shapes(self, s, w):
        a, x = _lru_inputs(jax.random.PRNGKey(s * 131 + w), (1, s, w))
        out = lru_scan(a, x)
        r = ref.lru_scan_ref(a, x)
        np.testing.assert_allclose(out, r, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("s,w", [(2, 1), (13, 5), (33, 16)])
    def test_random_shapes_smoke(self, s, w):
        """Deterministic slice of the shape property (no hypothesis needed):
        ragged sequence lengths and odd widths."""
        a, x = _lru_inputs(jax.random.PRNGKey(s * 131 + w), (1, s, w))
        out = lru_scan(a, x)
        r = ref.lru_scan_ref(a, x)
        np.testing.assert_allclose(out, r, rtol=1e-5, atol=1e-5)

    def test_initial_state(self):
        """``h0`` carries a chunk's state into the next (chunked prefill):
        the oracle sees it as one extra leading step whose input is h0."""
        a, x = _lru_inputs(jax.random.PRNGKey(7), (2, 24, 8))
        h0 = jax.random.normal(jax.random.PRNGKey(8), (2, 8))
        out = lru_scan(a, x, h0=h0)
        r = ref.lru_scan_ref(
            jnp.concatenate([jnp.ones_like(a[:, :1]), a], axis=1),
            jnp.concatenate([h0[:, None], x], axis=1),
        )[:, 1:]
        np.testing.assert_allclose(out, r, rtol=1e-5, atol=1e-5)


def _wkv_inputs(key, b, s, h, n):
    ks = jax.random.split(key, 5)
    r_ = jax.random.normal(ks[0], (b, s, h, n))
    k_ = jax.random.normal(ks[1], (b, s, h, n))
    v_ = jax.random.normal(ks[2], (b, s, h, n))
    lw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, n)) * 0.5)
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    return r_, k_, v_, lw, u


class TestWkv6:
    @pytest.mark.parametrize(
        "b,s,h,n,chunk", [(1, 16, 1, 8, 8), (2, 48, 2, 8, 16), (1, 50, 3, 16, 16)]
    )
    def test_vs_ref(self, b, s, h, n, chunk):
        args = _wkv_inputs(jax.random.PRNGKey(0), b, s, h, n)
        out, _ = chunked_wkv(*args, chunk=chunk)
        oracle = ref.wkv6_ref(*args)
        np.testing.assert_allclose(out, oracle, rtol=1e-4, atol=1e-4)

    def test_matches_model_chunked_path(self):
        """The chunked (training) and sequential (decode) paths agree on
        the outputs and on the state they hand on."""
        args = _wkv_inputs(jax.random.PRNGKey(1), 2, 32, 2, 8)
        a, sa = sequential_wkv(*args)
        bx, sb = chunked_wkv(*args, chunk=8)
        np.testing.assert_allclose(a, bx, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(sa, sb, rtol=1e-4, atol=1e-4)


class TestQuantize:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("r,c,rb", [(32, 64, 16), (100, 128, 32), (7, 256, 8)])
    def test_roundtrip(self, r, c, rb, dtype):
        x = (jax.random.normal(jax.random.PRNGKey(0), (r, c)) * 3).astype(dtype)
        q, s = ops.quantize(x, row_block=rb, interpret=True)
        qr, sr = ref.quantize_ref(x)
        # bf16 rounding can flip ties by one quantization level
        max_q_diff = 0 if dtype == jnp.float32 else 1
        assert (
            np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32)).max()
            <= max_q_diff
        )
        back = ops.dequantize(q, s, interpret=True)
        # int8 quantization error bound: absmax/127 per row (+ bf16 eps slack)
        err = np.abs(np.asarray(back, np.float32) - np.asarray(x, np.float32))
        slack = 0.51 if dtype == jnp.float32 else 1.6
        bound = np.asarray(sr)[:, 0] * slack + 1e-6
        assert (err <= bound[:, None]).all()

    def test_quantization_error_bound_property(self):
        for seed in range(5):
            x = jax.random.normal(jax.random.PRNGKey(seed), (16, 32)) * (seed + 1)
            q, s = ops.quantize(x, row_block=8, interpret=True)
            back = ops.dequantize(q, s, interpret=True)
            scale = np.asarray(s)
            assert np.abs(np.asarray(back - x)).max() <= scale.max() * 0.51
