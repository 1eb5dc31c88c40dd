"""Fused WKV Pallas kernel vs sequential/chunked oracles + shared carry
helpers + gradient parity for the custom-VJP reverse elevator sweep."""

import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.wkv.ops as wkv_ops
import repro.kernels.wkv.vjp as wkv_vjp
from repro.kernels.common import (
    cumsum_rows,
    halving_chunk,
    largest_divisor_chunk,
    pick_d_block,
    rev_cumsum_rows,
    reversed_chunk,
    shift_rows,
    validate_divisible,
)
from repro.kernels.wkv.bwd import wkv_pallas_bwd
from repro.kernels.wkv.kernel import wkv_pallas, wkv_pallas_train
from repro.kernels.wkv.ops import resolve_chunk, wkv_fused
from repro.kernels.wkv.ref import (
    wkv_chunked_bwd_ref,
    wkv_chunked_ref,
    wkv_sequential_ref,
)

jax.config.update("jax_platform_name", "cpu")


def _wkv_inputs(b, h, t, dh, seed=0, zero_h0=False):
    rng = np.random.default_rng(seed)
    r = jnp.asarray(rng.standard_normal((b, h, t, dh)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, h, t, dh)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, h, t, dh)).astype(np.float32))
    # Decay in the Finch regime (|log w| small enough for the ratio trick).
    w = jnp.asarray(rng.uniform(0.85, 0.999, (b, h, t, dh)).astype(np.float32))
    u = jnp.asarray(rng.standard_normal((h, dh)).astype(np.float32))
    h0 = (
        jnp.zeros((b, h, dh, dh), jnp.float32)
        if zero_h0
        else jnp.asarray(rng.standard_normal((b, h, dh, dh)).astype(np.float32))
    )
    return r, k, v, w, u, h0


def _assert_wkv_close(got, want, tol=1e-4):
    out_g, s_g = got
    out_w, s_w = want
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_w),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(s_g), np.asarray(s_w),
                               rtol=tol, atol=tol)


class TestWKVKernel:
    def test_acceptance_shape_nonzero_h0(self):
        # The acceptance-criteria shape: (B=2, H=4, T=256, Dh=64), h0 != 0.
        args = _wkv_inputs(2, 4, 256, 64)
        got = wkv_pallas(*args, chunk=32, interpret=True)
        _assert_wkv_close(got, wkv_sequential_ref(*args))

    def test_decode_t1(self):
        args = _wkv_inputs(2, 2, 1, 64, seed=1)
        got = wkv_pallas(*args, chunk=1, interpret=True)
        _assert_wkv_close(got, wkv_sequential_ref(*args))

    def test_multi_head_small(self):
        args = _wkv_inputs(1, 8, 64, 16, seed=2)
        got = wkv_pallas(*args, chunk=16, interpret=True)
        _assert_wkv_close(got, wkv_sequential_ref(*args))

    def test_chunk_invariance(self):
        # The VMEM state carry must make chunking invisible.
        args = _wkv_inputs(1, 2, 128, 32, seed=3)
        outs = [wkv_pallas(*args, chunk=c, interpret=True) for c in (8, 32, 128)]
        for got in outs[1:]:
            _assert_wkv_close(got, outs[0], tol=5e-5)

    def test_kernel_matches_chunked_ref(self):
        args = _wkv_inputs(2, 2, 128, 32, seed=4)
        got = wkv_pallas(*args, chunk=32, interpret=True)
        _assert_wkv_close(got, wkv_chunked_ref(*args, chunk=32))

    def test_rejects_bad_chunk(self):
        args = _wkv_inputs(1, 1, 96, 16, seed=5)
        with pytest.raises(ValueError):
            wkv_pallas(*args, chunk=64, interpret=True)


class TestWKVDispatch:
    def test_paths_agree(self):
        args = _wkv_inputs(2, 2, 128, 32, seed=6)
        jnp_path = wkv_fused(*args, chunk=32, use_kernel=False)
        kernel_path = wkv_fused(*args, chunk=32, use_kernel=True)
        ref = wkv_sequential_ref(*args)
        _assert_wkv_close(jnp_path, ref)
        _assert_wkv_close(kernel_path, ref)

    def test_odd_length_sequence(self):
        # T=17 (prime): dispatch must still be exact — the old code silently
        # rewrote chunk = t; now the largest valid divisor is picked.
        args = _wkv_inputs(1, 2, 17, 16, seed=7)
        for use_kernel in (False, True):
            got = wkv_fused(*args, chunk=64, use_kernel=use_kernel)
            _assert_wkv_close(got, wkv_sequential_ref(*args))

    def test_chunk_adjust_warns_once(self):
        # chunk=16 does not divide T=20 -> largest divisor (10) + warning,
        # fired once per (T, chunk): dispatch runs at trace time under the
        # outer jit, and a per-retrace warning is log spam.
        wkv_ops._CHUNK_WARNED.clear()
        with pytest.warns(UserWarning, match="does not divide"):
            assert resolve_chunk(20, 16) == 10
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert resolve_chunk(20, 16) == 10  # deduped
            # A different (T, chunk) pair still warns.
        with pytest.warns(UserWarning, match="does not divide"):
            assert resolve_chunk(40, 16) == 10
        wkv_ops._CHUNK_WARNED.clear()
        args = _wkv_inputs(1, 1, 20, 16, seed=8)
        with pytest.warns(UserWarning, match="does not divide"):
            got = wkv_fused(*args, chunk=16, use_kernel=False)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            got = wkv_fused(*args, chunk=16, use_kernel=False)
        _assert_wkv_close(got, wkv_sequential_ref(*args))
        wkv_ops._CHUNK_WARNED.clear()

    def test_repeated_warn_count_is_one(self):
        wkv_ops._CHUNK_WARNED.clear()
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            for _ in range(4):
                resolve_chunk(20, 16)
        assert len([w for w in rec if "does not divide" in str(w.message)]) == 1
        wkv_ops._CHUNK_WARNED.clear()

    def test_exact_chunk_does_not_warn(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert resolve_chunk(256, 64) == 64
            assert resolve_chunk(17, 64) == 17  # t < chunk: single chunk

    def test_nonpositive_chunk_raises(self):
        args = _wkv_inputs(1, 1, 8, 8, seed=11)
        for bad in (0, -4):
            with pytest.raises(ValueError, match="chunk must be >= 1"):
                wkv_fused(*args, chunk=bad)

    def test_tpu_kernel_chunk_must_tile(self):
        # The compiled kernel's (chunk, Dh) blocks need chunk % 8 == 0 or
        # chunk == T; a divisor that is neither raises instead of warning.
        assert resolve_chunk(256, 64, tpu_kernel=True) == 64
        assert resolve_chunk(17, 64, tpu_kernel=True) == 17
        with pytest.raises(ValueError, match="tiling"):
            resolve_chunk(20, 16, tpu_kernel=True)
        wkv_ops._CHUNK_WARNED.clear()
        with pytest.warns(UserWarning, match="does not divide"):
            assert resolve_chunk(20, 16) == 10  # off the chip: warns only

    def test_ref_raises_on_indivisible(self):
        args = _wkv_inputs(1, 1, 20, 16, seed=9)
        with pytest.raises(ValueError):
            wkv_chunked_ref(*args, chunk=16)

    def test_decode_h0_defaults_to_zeros(self):
        r, k, v, w, u, h0 = _wkv_inputs(1, 2, 1, 32, seed=10, zero_h0=True)
        got = wkv_fused(r, k, v, w, u, None)
        _assert_wkv_close(got, wkv_sequential_ref(r, k, v, w, u, h0))


class TestSharedCarryHelpers:
    def test_largest_divisor_chunk(self):
        assert largest_divisor_chunk(256, 64) == 64
        assert largest_divisor_chunk(20, 16) == 10
        assert largest_divisor_chunk(17, 16) == 1
        assert largest_divisor_chunk(17, 64) == 17

    def test_halving_chunk(self):
        assert halving_chunk(2048, 256) == 256
        assert halving_chunk(96, 64) == 32
        assert halving_chunk(8, 256) == 8

    def test_validate_divisible(self):
        validate_divisible("T", 128, 32)
        with pytest.raises(ValueError):
            validate_divisible("T", 128, 48)
        with pytest.raises(ValueError):
            validate_divisible("T", 128, 0)

    def test_pick_d_block(self):
        assert pick_d_block(256) == 256
        assert pick_d_block(1024) == 512
        with pytest.raises(ValueError):
            pick_d_block(768)

    def test_cumsum_rows_matches_cumsum(self):
        rng = np.random.default_rng(0)
        for rows in (1, 7, 8, 33):
            x = jnp.asarray(rng.standard_normal((rows, 16)).astype(np.float32))
            np.testing.assert_allclose(
                np.asarray(cumsum_rows(x, rows)),
                np.cumsum(np.asarray(x), axis=0),
                rtol=1e-5, atol=1e-5,
            )

    def test_shift_rows(self):
        x = jnp.arange(12.0).reshape(4, 3)
        out = np.asarray(shift_rows(x, 2, -1.0))
        np.testing.assert_array_equal(out[:2], -1.0)
        np.testing.assert_array_equal(out[2:], np.asarray(x)[:2])

    def test_shift_rows_negative_delta(self):
        # The reverse-sweep direction: rows move toward lower indices.
        x = jnp.arange(12.0).reshape(4, 3)
        out = np.asarray(shift_rows(x, -2, -1.0))
        np.testing.assert_array_equal(out[:2], np.asarray(x)[2:])
        np.testing.assert_array_equal(out[2:], -1.0)

    def test_rev_cumsum_rows_matches_suffix_sum(self):
        rng = np.random.default_rng(1)
        for rows in (1, 7, 8, 33):
            x = jnp.asarray(rng.standard_normal((rows, 16)).astype(np.float32))
            want = np.flip(np.cumsum(np.flip(np.asarray(x), 0), axis=0), 0)
            np.testing.assert_allclose(
                np.asarray(rev_cumsum_rows(x, rows)), want,
                rtol=1e-5, atol=1e-5,
            )

    def test_rev_cumsum_is_cumsum_adjoint(self):
        # If y = cumsum(x) then dx = rev_cumsum(dy) — the identity the
        # backward kernel leans on for the cumulative log-decay chains.
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
        dy = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
        _, vjp = jax.vjp(lambda v: cumsum_rows(v, 16), x)
        (want,) = vjp(dy)
        np.testing.assert_allclose(
            np.asarray(rev_cumsum_rows(dy, 16)), np.asarray(want),
            rtol=1e-5, atol=1e-5,
        )

    def test_reversed_chunk(self):
        rev = reversed_chunk(8)
        assert [rev(s) for s in range(8)] == [7, 6, 5, 4, 3, 2, 1, 0]


class TestCostModel:
    def test_wkv_bwd_traffic_ordering(self):
        from repro.core.cost_model import wkv_bwd_traffic, wkv_traffic

        naive, shared, direct = wkv_bwd_traffic(4, 4, 2048, 64, chunk=64)
        assert [c.variant for c in (naive, shared, direct)] == [
            "naive", "shared", "direct"]
        assert all(c.name == "wkv_bwd" for c in (naive, shared, direct))
        # The whole point of the reverse sweep: the kernel path stages only
        # the chunk-entry states, a small fraction of the autodiff
        # residuals, so modeled energy strictly improves.
        assert direct.energy_pj < shared.energy_pj < naive.energy_pj
        assert direct.traffic.scratchpad_bytes < shared.traffic.scratchpad_bytes / 10
        # Backward moves more bytes than forward on every variant.
        f_naive, f_shared, f_direct = wkv_traffic(4, 4, 2048, 64, chunk=64)
        assert shared.traffic.scratchpad_bytes > f_shared.traffic.scratchpad_bytes
        assert direct.traffic.dram_bytes > f_direct.traffic.dram_bytes


# ==========================================================================
# Gradient parity: kernel VJP vs jax.grad of the sequential oracle
# ==========================================================================

def _vjp_grads(fn, args, seed=100):
    """Full cotangent pull-back of (out, S_out) through ``fn``."""
    out, vjp = jax.vjp(fn, *args)
    rng = np.random.default_rng(seed)
    cts = tuple(
        jnp.asarray(rng.standard_normal(o.shape).astype(np.float32)).astype(o.dtype)
        for o in out
    )
    return vjp(cts)


def _assert_grads_close(got, want, tol=2e-3):
    for name, g, w in zip(("dr", "dk", "dv", "dw", "du", "dh0"), got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=tol, atol=tol,
            err_msg=f"gradient mismatch for {name}",
        )


class TestWKVGrad:
    def test_kernel_vjp_matches_sequential_autodiff(self):
        # Nonzero h0 — every cotangent including du and dh0.
        args = _wkv_inputs(2, 2, 128, 32, seed=20)
        got = _vjp_grads(
            lambda *a: wkv_fused(*a, chunk=32, use_kernel=True), args)
        want = _vjp_grads(lambda *a: wkv_sequential_ref(*a), args)
        _assert_grads_close(got, want)

    def test_jnp_path_vjp_matches_sequential_autodiff(self):
        args = _wkv_inputs(2, 2, 128, 32, seed=21)
        got = _vjp_grads(
            lambda *a: wkv_fused(*a, chunk=32, use_kernel=False), args)
        want = _vjp_grads(lambda *a: wkv_sequential_ref(*a), args)
        _assert_grads_close(got, want)

    def test_chunked_bwd_ref_matches_sequential_autodiff(self):
        # The jnp oracle for the reverse kernel, called directly.
        r, k, v, w, u, h0 = _wkv_inputs(2, 2, 64, 16, seed=22)
        rng = np.random.default_rng(23)
        d_out = jnp.asarray(
            rng.standard_normal((2, 2, 64, 16)).astype(np.float32))
        d_S = jnp.asarray(
            rng.standard_normal((2, 2, 16, 16)).astype(np.float32))
        got = wkv_chunked_bwd_ref(r, k, v, w, u, h0, d_out, d_S, chunk=16)
        _, vjp = jax.vjp(lambda *a: wkv_sequential_ref(*a), r, k, v, w, u, h0)
        want = vjp((d_out, d_S))
        _assert_grads_close(got, want)

    def test_grad_chunk_invariance(self):
        # Gradients, like outputs, must not see the chunking.
        args = _wkv_inputs(1, 2, 128, 32, seed=24)
        grads = [
            _vjp_grads(
                lambda *a, c=c: wkv_fused(*a, chunk=c, use_kernel=True), args)
            for c in (8, 32, 128)
        ]
        for got in grads[1:]:
            _assert_grads_close(got, grads[0], tol=1e-3)

    def test_grad_odd_length_fallback_chunk(self):
        # T=20 with chunk=16 -> fallback divisor 10; T=17 (prime, > chunk)
        # -> degenerate chunk=1, i.e. 17 single-token chunks.  Both must
        # still differentiate exactly.
        wkv_ops._CHUNK_WARNED.clear()
        for t in (20, 17):
            args = _wkv_inputs(1, 2, t, 16, seed=25 + t)
            want = _vjp_grads(lambda *a: wkv_sequential_ref(*a), args)
            for use_kernel in (True, False):
                got = _vjp_grads(
                    lambda *a: wkv_fused(*a, chunk=16, use_kernel=use_kernel),
                    args)
                _assert_grads_close(got, want)
        wkv_ops._CHUNK_WARNED.clear()

    def test_grad_decode_t1(self):
        args = _wkv_inputs(1, 2, 1, 16, seed=30)
        want = _vjp_grads(lambda *a: wkv_sequential_ref(*a), args)
        for use_kernel in (True, False):
            got = _vjp_grads(
                lambda *a: wkv_fused(*a, chunk=16, use_kernel=use_kernel), args)
            _assert_grads_close(got, want)

    def test_pallas_bwd_matches_chunked_bwd_ref(self):
        # Kernel vs its jnp oracle on identical cotangents, via s_hist from
        # the training forward.
        r, k, v, w, u, h0 = _wkv_inputs(2, 2, 64, 16, seed=31)
        rng = np.random.default_rng(32)
        d_out = jnp.asarray(
            rng.standard_normal((2, 2, 64, 16)).astype(np.float32))
        d_S = jnp.asarray(
            rng.standard_normal((2, 2, 16, 16)).astype(np.float32))
        out, s_out, s_hist = wkv_pallas_train(
            r, k, v, w, u, h0, chunk=16, interpret=True)
        dr, dk, dv, dw, du_part, dh0 = wkv_pallas_bwd(
            r, k, v, w, u, s_hist, d_out, d_S, chunk=16, interpret=True)
        got = (dr, dk, dv, dw, du_part.sum(axis=0), dh0)
        want = wkv_chunked_bwd_ref(r, k, v, w, u, h0, d_out, d_S, chunk=16)
        _assert_grads_close(got, want, tol=5e-4)

    def test_train_forward_emits_entry_states(self):
        # s_hist[c] must equal the state the plain forward would enter
        # chunk c with: s_hist[0] == h0, s_hist[c] == exit state of the
        # (truncated) forward over chunks < c.
        args = _wkv_inputs(1, 2, 64, 16, seed=33)
        r, k, v, w, u, h0 = args
        out_t, s_t, s_hist = wkv_pallas_train(*args, chunk=16, interpret=True)
        out_p, s_p = wkv_pallas(*args, chunk=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out_t), np.asarray(out_p),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s_t), np.asarray(s_p),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s_hist[:, :, 0]),
                                   np.asarray(h0), rtol=1e-5, atol=1e-5)
        for c in (1, 2, 3):
            _, s_prefix = wkv_sequential_ref(
                r[:, :, : 16 * c], k[:, :, : 16 * c], v[:, :, : 16 * c],
                w[:, :, : 16 * c], u, h0)
            np.testing.assert_allclose(
                np.asarray(s_hist[:, :, c]), np.asarray(s_prefix),
                rtol=2e-4, atol=2e-4)


# ==========================================================================
# Dispatch: auto mode must pick the kernel on TPU (regression: the old
# code mapped use_kernel=None to False, so auto could never select it)
# ==========================================================================

class TestAutoDispatch:
    def _fake_tpu(self, monkeypatch):
        # Pretend the backend is TPU but keep Pallas in interpret mode so
        # the kernel actually runs on this container.
        monkeypatch.setattr(wkv_ops, "on_tpu", lambda: True)
        monkeypatch.setattr(wkv_ops, "interpret_default", lambda: True)

    def test_auto_picks_kernel_forward(self, monkeypatch):
        self._fake_tpu(monkeypatch)
        calls = []
        real = wkv_vjp.wkv_pallas
        monkeypatch.setattr(
            wkv_vjp, "wkv_pallas",
            lambda *a, **kw: calls.append("fwd") or real(*a, **kw))
        args = _wkv_inputs(1, 2, 64, 16, seed=40)
        got = wkv_fused(*args, chunk=16, use_kernel=None)
        assert calls == ["fwd"], "auto mode did not select the Pallas kernel"
        _assert_wkv_close(got, wkv_sequential_ref(*args))

    def test_auto_picks_kernel_for_training(self, monkeypatch):
        self._fake_tpu(monkeypatch)
        calls = []
        real_train = wkv_vjp.wkv_pallas_train
        real_bwd = wkv_vjp.wkv_pallas_bwd
        monkeypatch.setattr(
            wkv_vjp, "wkv_pallas_train",
            lambda *a, **kw: calls.append("train_fwd") or real_train(*a, **kw))
        monkeypatch.setattr(
            wkv_vjp, "wkv_pallas_bwd",
            lambda *a, **kw: calls.append("bwd") or real_bwd(*a, **kw))
        args = _wkv_inputs(1, 2, 64, 16, seed=41)
        got = _vjp_grads(lambda *a: wkv_fused(*a, chunk=16, use_kernel=None),
                         args)
        assert calls == ["train_fwd", "bwd"], (
            "auto mode did not run the kernel VJP pair under jax.grad")
        want = _vjp_grads(lambda *a: wkv_sequential_ref(*a), args)
        _assert_grads_close(got, want)

    def test_apply_rwkv_block_auto_reaches_kernel(self, monkeypatch):
        # End-to-end: the model block with use_kernel=None (the default)
        # must reach the Pallas path under TPU/interpret — including a
        # gradient through it.
        from repro.model import recurrent as rec

        self._fake_tpu(monkeypatch)
        calls = []
        real_train = wkv_vjp.wkv_pallas_train
        real_bwd = wkv_vjp.wkv_pallas_bwd
        monkeypatch.setattr(
            wkv_vjp, "wkv_pallas_train",
            lambda *a, **kw: calls.append("train_fwd") or real_train(*a, **kw))
        monkeypatch.setattr(
            wkv_vjp, "wkv_pallas_bwd",
            lambda *a, **kw: calls.append("bwd") or real_bwd(*a, **kw))

        d = 64  # one WKV head
        rng = np.random.default_rng(42)
        mk = lambda shape, scale=0.1: jnp.asarray(  # noqa: E731
            rng.standard_normal(shape).astype(np.float32) * scale)
        params = {
            "mu": mk((5, d)),
            "w_r": mk((d, d)), "w_k": mk((d, d)),
            "w_v": mk((d, d)), "w_g": mk((d, d)),
            "w_decay_base": mk((d,)),
            "w_decay_lora_a": mk((d, 64)),
            "w_decay_lora_b": mk((64, d)),
            "u_bonus": mk((d,)),
            "w_o": mk((d, d)),
            "out_norm": {"scale": jnp.ones((d,), jnp.float32)},
        }
        cfg = types.SimpleNamespace(fsdp_gather_weights=False, norm_eps=1e-6)
        x = mk((2, 32, d), scale=1.0)

        def loss(p, x_):
            out, _ = rec.apply_rwkv_block(p, x_, cfg, chunk=16)
            return (out * out).sum()

        grads = jax.grad(loss)(params, x)
        assert calls == ["train_fwd", "bwd"], (
            "apply_rwkv_block auto mode did not take the kernel VJP path")

        # Parity: same loss/grads as the forced-jnp path.
        calls.clear()
        monkeypatch.setattr(wkv_ops, "on_tpu", lambda: False)

        def loss_jnp(p, x_):
            out, _ = rec.apply_rwkv_block(p, x_, cfg, chunk=16,
                                          use_kernel=False)
            return (out * out).sum()

        grads_jnp = jax.grad(loss_jnp)(params, x)
        flat, _ = jax.tree.flatten(grads)
        flat_jnp, _ = jax.tree.flatten(grads_jnp)
        for g, gj in zip(flat, flat_jnp):
            np.testing.assert_allclose(np.asarray(g), np.asarray(gj),
                                       rtol=2e-3, atol=2e-3)


# ==========================================================================
# bf16 I/O through dispatch (no caller-side upcast) + segment summaries
# ==========================================================================

class TestWKVBf16:
    """r/k/v/w may arrive in bf16: f32 accumulation inside, input dtype out."""

    def _bf16_inputs(self, *shape_args, **kw):
        r, k, v, w, u, h0 = _wkv_inputs(*shape_args, **kw)
        bf = jnp.bfloat16
        return r.astype(bf), k.astype(bf), v.astype(bf), w.astype(bf), u, h0

    def test_jnp_dispatch_bf16_parity(self):
        args32 = _wkv_inputs(2, 2, 64, 16, seed=60)
        args16 = self._bf16_inputs(2, 2, 64, 16, seed=60)
        out32, s32 = wkv_fused(*args32, chunk=16, use_kernel=False)
        out16, s16 = wkv_fused(*args16, chunk=16, use_kernel=False)
        assert out16.dtype == jnp.bfloat16
        assert s16.dtype == jnp.float32  # state stays full precision
        # bf16 inputs quantize the operands (~2^-8 relative); the f32
        # accumulation keeps the error at the input-rounding level.
        np.testing.assert_allclose(
            np.asarray(out16, dtype=np.float32), np.asarray(out32),
            rtol=0.1, atol=0.1)
        np.testing.assert_allclose(
            np.asarray(s16), np.asarray(s32), rtol=0.1, atol=0.15)

    def test_kernel_interpret_bf16_parity(self):
        args16 = self._bf16_inputs(1, 2, 64, 16, seed=61)
        out_k, s_k = wkv_fused(*args16, chunk=16, use_kernel=True)
        out_j, s_j = wkv_fused(*args16, chunk=16, use_kernel=False)
        assert out_k.dtype == jnp.bfloat16
        # Same bf16 inputs on both backends: kernel vs jnp agree tightly.
        np.testing.assert_allclose(
            np.asarray(out_k, dtype=np.float32),
            np.asarray(out_j, dtype=np.float32), rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_j),
                                   rtol=2e-2, atol=2e-2)

    def test_decode_t1_bf16(self):
        args16 = self._bf16_inputs(2, 2, 1, 16, seed=62)
        out, s = wkv_fused(*args16, chunk=16, use_kernel=False)
        assert out.dtype == jnp.bfloat16 and s.dtype == jnp.float32

    def test_grads_come_back_in_input_dtypes(self):
        args16 = self._bf16_inputs(1, 2, 32, 16, seed=63)
        grads = _vjp_grads(
            lambda *a: wkv_fused(*a, chunk=16, use_kernel=False), args16)
        dtypes = [g.dtype for g in grads]
        assert dtypes[:4] == [jnp.bfloat16] * 4, dtypes
        assert dtypes[4] == jnp.float32 and dtypes[5] == jnp.float32

    def test_model_block_passes_bf16_through(self, monkeypatch):
        # apply_rwkv_block must not upcast before dispatch: the dtype
        # reaching wkv_fused is the model dtype.
        from repro.model import recurrent as rec

        seen = {}
        real = rec.wkv_fused

        def spy(r, *a, **kw):
            seen["dtype"] = r.dtype
            return real(r, *a, **kw)

        monkeypatch.setattr(rec, "wkv_fused", spy)
        d = 64
        rng = np.random.default_rng(64)
        mk = lambda shape, scale=0.1: jnp.asarray(  # noqa: E731
            rng.standard_normal(shape).astype(np.float32) * scale
        ).astype(jnp.bfloat16)
        params = {
            "mu": mk((5, d)),
            "w_r": mk((d, d)), "w_k": mk((d, d)),
            "w_v": mk((d, d)), "w_g": mk((d, d)),
            "w_decay_base": mk((d,)),
            "w_decay_lora_a": mk((d, 64)),
            "w_decay_lora_b": mk((64, d)),
            "u_bonus": mk((d,)),
            "w_o": mk((d, d)),
            "out_norm": {"scale": jnp.ones((d,), jnp.bfloat16)},
        }
        cfg = types.SimpleNamespace(fsdp_gather_weights=False, norm_eps=1e-6)
        x = mk((1, 32, d), scale=1.0)
        out, _ = rec.apply_rwkv_block(params, x, cfg, chunk=16,
                                      use_kernel=False)
        assert seen["dtype"] == jnp.bfloat16
        assert out.dtype == jnp.bfloat16


class TestWKVSummary:
    """The (decay-product, exit-state) segment summary: kernel emit, jnp
    oracle, and the linearity identity the sequence-parallel path uses."""

    def test_segment_decay_matches_kernel_emit(self):
        from repro.kernels.wkv.kernel import wkv_pallas_summary
        from repro.kernels.wkv.ref import wkv_segment_decay

        args = _wkv_inputs(2, 2, 64, 16, seed=70)
        out, s, a = wkv_pallas_summary(*args, chunk=16, interpret=True)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(wkv_segment_decay(args[3])),
            rtol=1e-5, atol=1e-5)
        # out/s unchanged vs the plain forward.
        _assert_wkv_close((out, s), wkv_sequential_ref(*args))

    def test_summary_composition_identity(self):
        # The protocol's core identity: running from entering state h0 ==
        # running from zero + the (A, S) composition + entry correction.
        from repro.kernels.wkv.ops import wkv_fused_summary
        from repro.kernels.wkv.ref import wkv_entry_correction

        r, k, v, w, u, h0 = _wkv_inputs(2, 2, 64, 16, seed=71)
        out0, s0, a_seg = wkv_fused_summary(r, k, v, w, u, None, chunk=16,
                                            use_kernel=False)
        out_h, s_h = wkv_fused(r, k, v, w, u, h0, chunk=16, use_kernel=False)
        out_fix = out0 + wkv_entry_correction(r, w, h0)
        s_fix = a_seg[..., :, None] * h0 + s0
        np.testing.assert_allclose(np.asarray(out_fix), np.asarray(out_h),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(s_fix), np.asarray(s_h),
                                   rtol=2e-4, atol=2e-4)

    def test_summary_grads_match_reference(self):
        # d_a cotangent folds into dw in closed form; check against jax.grad
        # of a pure-jnp rendering (sequential scan + explicit decay product).
        from repro.kernels.wkv.ops import wkv_fused_summary

        r, k, v, w, u, h0 = _wkv_inputs(1, 2, 32, 16, seed=72)

        def f_sum(use_kernel):
            def f(*args):
                out, s, a = wkv_fused_summary(*args, chunk=16,
                                              use_kernel=use_kernel)
                return out.sum() + (s * s).sum() + (a * a * 3.0).sum()
            return f

        def f_ref(r_, k_, v_, w_, u_, h0_):
            out, s = wkv_sequential_ref(r_, k_, v_, w_, u_, h0_)
            logw = jnp.log(jnp.clip(w_, 1e-8, 1.0))
            a = jnp.exp(jnp.sum(logw, axis=2))
            return out.sum() + (s * s).sum() + (a * a * 3.0).sum()

        argnums = tuple(range(6))
        want = jax.grad(f_ref, argnums=argnums)(r, k, v, w, u, h0)
        for use_kernel in (False, True):
            got = jax.grad(f_sum(use_kernel), argnums=argnums)(
                r, k, v, w, u, h0)
            _assert_grads_close(got, want)

    def test_seqshard_cost_model_ordering(self):
        from repro.core.cost_model import wkv_seqshard_traffic

        naive, shared, direct = wkv_seqshard_traffic(4, 4, 8192, 64, 8)
        assert [c.variant for c in (naive, shared, direct)] == [
            "naive", "shared", "direct"]
        # O(Dh²) summary hops vs O(T·D) token re-gather: orders of
        # magnitude fewer bytes cross the seq axis.
        crossed_naive = naive.traffic.dram_bytes
        crossed_direct = direct.traffic.fabric_bytes
        assert crossed_direct * 50 < crossed_naive
        assert direct.energy_pj < shared.energy_pj < naive.energy_pj
        # Summary bytes are independent of T.
        _, _, direct_long = wkv_seqshard_traffic(4, 4, 4 * 8192, 64, 8)
        assert direct_long.traffic.fabric_bytes == crossed_direct


class TestWKVDecodeKernel:
    """Persistent-state decode micro-kernels (kernels/wkv/decode)."""

    def test_single_step_parity_nonzero_state(self):
        from repro.kernels.wkv.decode import wkv_decode_pallas

        args = _wkv_inputs(2, 3, 1, 32, seed=70)  # h0 != 0 by default
        got = wkv_decode_pallas(*args, interpret=True)
        _assert_wkv_close(got, wkv_sequential_ref(*args))

    def test_window_parity_odd_k(self):
        # K not dividing anything (prime, > any chunk): the window kernel
        # has no divisibility constraint.
        from repro.kernels.wkv.decode import wkv_decode_window_pallas

        for k_win in (1, 5, 37):
            args = _wkv_inputs(2, 2, k_win, 16, seed=71)
            got = wkv_decode_window_pallas(*args, interpret=True)
            _assert_wkv_close(got, wkv_sequential_ref(*args))

    def test_state_carry_across_consecutive_windows(self):
        # Chaining windows through S_out must equal the one-shot sweep —
        # the serve-loop contract (state carried between dispatches).
        from repro.kernels.wkv.decode import wkv_decode_window_pallas

        r, k, v, w, u, h0 = _wkv_inputs(2, 2, 37, 16, seed=72)
        one_out, one_s = wkv_decode_window_pallas(
            r, k, v, w, u, h0, interpret=True)
        outs, s = [], h0
        for lo, hi in ((0, 16), (16, 32), (32, 37)):
            o, s = wkv_decode_window_pallas(
                r[:, :, lo:hi], k[:, :, lo:hi], v[:, :, lo:hi],
                w[:, :, lo:hi], u, s, interpret=True)
            outs.append(o)
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate(outs, axis=2)), np.asarray(one_out),
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s), np.asarray(one_s),
                                   rtol=1e-5, atol=1e-5)

    def test_window_bf16_io(self):
        from repro.kernels.wkv.decode import wkv_decode_window_pallas

        r, k, v, w, u, h0 = _wkv_inputs(2, 2, 9, 16, seed=73)
        bf = jnp.bfloat16
        got_o, got_s = wkv_decode_window_pallas(
            r.astype(bf), k.astype(bf), v.astype(bf), w.astype(bf),
            u.astype(bf), h0, interpret=True)
        assert got_o.dtype == bf
        assert got_s.dtype == jnp.float32  # state stays full precision
        want_o, want_s = wkv_sequential_ref(
            r.astype(bf).astype(jnp.float32), k.astype(bf).astype(jnp.float32),
            v.astype(bf).astype(jnp.float32), w.astype(bf).astype(jnp.float32),
            u.astype(bf).astype(jnp.float32), h0)
        np.testing.assert_allclose(
            np.asarray(got_o, dtype=np.float32), np.asarray(want_o),
            rtol=0.1, atol=0.1)
        np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                                   rtol=2e-2, atol=2e-2)

    def test_decode_grads_match_sequential_autodiff(self):
        args = _wkv_inputs(1, 2, 7, 16, seed=74)
        want = _vjp_grads(lambda *a: wkv_sequential_ref(*a), args)
        for use_kernel in (True, False):
            got = _vjp_grads(
                lambda *a: wkv_fused(*a, decode=True, use_kernel=use_kernel),
                args)
            _assert_grads_close(got, want)

    def test_dispatch_decode_routes_to_decode_kernel(self, monkeypatch):
        # decode=True windows <= DECODE_WINDOW_MAX take the decode kernel;
        # longer stateful sweeps fall through to the chunked elevator path.
        import repro.kernels.wkv.decode as wkv_decode

        calls = []
        real_win = wkv_decode.wkv_decode_window_pallas
        real_one = wkv_decode.wkv_decode_pallas
        monkeypatch.setattr(
            wkv_ops, "wkv_decode_diff",
            lambda *a, **kw: calls.append("decode")
            or wkv_decode.wkv_decode_diff(*a, **kw))
        monkeypatch.setattr(
            wkv_decode, "wkv_decode_window_pallas",
            lambda *a, **kw: calls.append("window") or real_win(*a, **kw))
        monkeypatch.setattr(
            wkv_decode, "wkv_decode_pallas",
            lambda *a, **kw: calls.append("single") or real_one(*a, **kw))

        args = _wkv_inputs(1, 2, 8, 16, seed=75)
        wkv_fused(*args, decode=True, use_kernel=True)
        assert calls == ["decode", "window"]

        calls.clear()
        args1 = _wkv_inputs(1, 2, 1, 16, seed=76)
        wkv_fused(*args1, use_kernel=True)  # t==1 infers decode=True
        assert calls == ["decode", "single"]

        calls.clear()
        args_long = _wkv_inputs(1, 2, 128, 16, seed=77)
        got = wkv_fused(*args_long, chunk=16, decode=True, use_kernel=True)
        assert calls == []  # chunked path, not the decode kernel
        _assert_wkv_close(got, wkv_sequential_ref(*args_long))

    def test_training_path_unaffected_by_decode_default(self):
        # decode=None + t > 1 must keep the chunked (training) route.
        args = _wkv_inputs(1, 2, 32, 16, seed=78)
        got = wkv_fused(*args, chunk=16, use_kernel=False)
        _assert_wkv_close(got, wkv_chunked_ref(*args, chunk=16))

    def test_decode_cost_model_per_token_state_bytes(self):
        # Acceptance: modeled per-token state bytes drop ~K× at K=32.
        from repro.core.cost_model import (
            wkv_decode_token_io,
            wkv_decode_traffic,
        )

        b, h, dh, k = 4, 4, 64, 32
        naive, shared, direct = wkv_decode_traffic(b, h, dh, k)
        assert [c.variant for c in (naive, shared, direct)] == [
            "naive", "shared", "direct"]
        tok_io = wkv_decode_token_io(b, h, dh, k)
        naive_state = naive.traffic.dram_bytes - tok_io
        direct_state = direct.traffic.dram_bytes - tok_io
        assert naive_state == k * direct_state
        assert direct.energy_pj < shared.energy_pj < naive.energy_pj
        # K=1 degenerates to the per-token pattern: no fabric traffic.
        _, _, direct1 = wkv_decode_traffic(b, h, dh, 1)
        assert direct1.traffic.fabric_bytes == 0
