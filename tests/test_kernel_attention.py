"""flash attention kernel vs jnp oracle: causal/full/window x GQA sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.local_attention.kernel import flash_attention_pallas
from repro.kernels.local_attention.ref import attention_ref

jax.config.update("jax_platform_name", "cpu")


def _qkv(b, hq, hkv, t, s, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, hq, t, d)).astype(np.float32)).astype(dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32)).astype(dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32)).astype(dtype)
    return q, k, v


def _check(out, ref, dtype):
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("t", [128, 256, 384])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_causal_full(t, dtype):
    q, k, v = _qkv(1, 2, 2, t, t, 128, dtype, seed=t)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    _check(out, ref, dtype)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
def test_gqa_groups(hq, hkv):
    q, k, v = _qkv(2, hq, hkv, 256, 256, 128, np.float32, seed=hq * 10 + hkv)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    _check(out, ref, np.float32)


@pytest.mark.parametrize("window", [128, 256, 512])
def test_sliding_window(window):
    t = 768
    q, k, v = _qkv(1, 2, 1, t, t, 128, np.float32, seed=window)
    out = flash_attention_pallas(q, k, v, causal=True, window=window, interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    _check(out, ref, np.float32)


def test_window_larger_than_seq_equals_causal():
    q, k, v = _qkv(1, 2, 2, 256, 256, 128, np.float32, seed=7)
    out = flash_attention_pallas(q, k, v, causal=True, window=4096, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    _check(out, ref, np.float32)


def test_non_causal_full_cross_attention():
    # Encoder / cross-attention: t != s, no mask.
    q, k, v = _qkv(2, 4, 4, 128, 384, 64, np.float32, seed=11)
    out = flash_attention_pallas(q, k, v, causal=False, interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    _check(out, ref, np.float32)


def test_unpadded_lengths():
    # T, S not multiples of the block size -> padding + masking path.
    q, k, v = _qkv(1, 2, 2, 200, 200, 64, np.float32, seed=13)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    _check(out, ref, np.float32)


def test_decode_alignment():
    # Decode: 1 query against a long KV cache; diagonal at the cache end.
    q, k, v = _qkv(2, 4, 2, 1, 512, 64, np.float32, seed=17)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    _check(out, ref, np.float32)


def test_windowed_decode():
    q, k, v = _qkv(1, 2, 1, 1, 1024, 64, np.float32, seed=19)
    out = flash_attention_pallas(q, k, v, causal=True, window=256, interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=256)
    _check(out, ref, np.float32)


def test_window_traffic_scales_with_window_not_seq():
    # Structural property: the kv-step count (grid dim 2) is O(window), not
    # O(T) — the transmission-window guarantee.
    from repro.kernels.local_attention import kernel as kmod

    t = 4096
    for window, expected in [(256, (256 + 128) // 128 + 2), (512, (512 + 128) // 128 + 2)]:
        n_kv_blocks = t // 128
        n_steps = min(n_kv_blocks, (window + 128) // 128 + 2)
        assert n_steps == expected
        assert n_steps < n_kv_blocks


class TestBlockwise:
    """attention_blockwise (dry-run lowering path) vs exact reference."""

    @pytest.mark.parametrize("t,window", [(300, None), (513, None), (700, 256), (1024, 128)])
    def test_matches_ref(self, t, window):
        from repro.kernels.local_attention.ref import attention_blockwise

        q, k, v = _qkv(1, 4, 2, t, t, 64, np.float32, seed=t)
        out = attention_blockwise(q, k, v, causal=True, window=window, block=128)
        ref = attention_ref(q, k, v, causal=True, window=window)
        _check(out, ref, np.float32)

    def test_non_causal(self):
        from repro.kernels.local_attention.ref import attention_blockwise

        q, k, v = _qkv(2, 2, 2, 200, 300, 64, np.float32, seed=5)
        out = attention_blockwise(q, k, v, causal=False, block=128)
        ref = attention_ref(q, k, v, causal=False)
        _check(out, ref, np.float32)

    def test_decode_against_cache(self):
        from repro.kernels.local_attention.ref import attention_blockwise

        q, k, v = _qkv(1, 4, 4, 1, 777, 64, np.float32, seed=9)
        out = attention_blockwise(q, k, v, causal=True, block=256)
        ref = attention_ref(q, k, v, causal=True)
        _check(out, ref, np.float32)

    def test_windowed_flops_scale_with_window(self):
        # The banded sweep must not visit all kv blocks.  Measured in
        # unrolled-cost mode (rolled scans hide trip counts from
        # cost_analysis) with fresh closures (jit caches by fn identity).
        from repro.kernels.local_attention.ref import attention_blockwise
        from repro.core.lowering import unrolled_cost_mode
        import jax

        def make(t, window):
            q, k, v = _qkv(1, 1, 1, t, t, 64, np.float32, seed=1)

            def f(a, b, c):
                return attention_blockwise(
                    a, b, c, causal=True, window=window, block=256
                )

            with unrolled_cost_mode():
                compiled = jax.jit(f).lower(q, k, v).compile()
                return compiled.cost_analysis()["flops"]

        f_small = make(4096, 256)
        f_big = make(4096, 2048)
        assert f_big > 2.5 * f_small  # grows with window
