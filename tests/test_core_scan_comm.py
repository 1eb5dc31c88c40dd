"""Tests for chunk_scan, device_comm (shard_map), pipeline, scratchpad."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import (
    DIAG_STATE,
    ELEMENTWISE,
    SharedBuffer,
    barrier,
    chunked_linear_scan,
    device_linear_scan_carry,
    device_shift,
    halo_exchange,
    linear_scan,
    pipeline_apply,
    ring_pass,
    seq_carry_scan,
)

jax.config.update("jax_platform_name", "cpu")


def ref_linear_scan(a, b, h0=0.0):
    h = np.zeros_like(b)
    prev = np.broadcast_to(np.asarray(h0, b.dtype), b.shape[1:]).copy()
    for t in range(b.shape[0]):
        prev = a[t] * prev + b[t]
        h[t] = prev
    return h


class TestLinearScan:
    def test_prefix_sum_is_special_case(self):
        # Paper Fig. 6: prefix sum == linear scan with a == 1.
        b = jnp.arange(1.0, 9.0)
        h = linear_scan(jnp.ones_like(b), b)
        np.testing.assert_allclose(h, np.cumsum(np.asarray(b)), rtol=1e-6)

    @given(
        t=st.sampled_from([4, 8, 16, 32]),
        chunk=st.sampled_from([2, 4, 8]),
        seed=st.integers(0, 2**31 - 1),
    )
    @settings(max_examples=25, deadline=None)
    def test_chunked_matches_flat(self, t, chunk, seed):
        if t % chunk:
            return
        rng = np.random.default_rng(seed)
        a = rng.uniform(0.5, 1.0, (t, 3)).astype(np.float32)
        b = rng.standard_normal((t, 3)).astype(np.float32)
        flat = linear_scan(jnp.asarray(a), jnp.asarray(b))
        chunked = chunked_linear_scan(jnp.asarray(a), jnp.asarray(b), chunk=chunk)
        np.testing.assert_allclose(np.asarray(flat), np.asarray(chunked), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(flat), ref_linear_scan(a, b), rtol=2e-4, atol=2e-4)

    def test_h0_injection(self):
        a = jnp.full((4,), 0.5)
        b = jnp.ones((4,))
        h = chunked_linear_scan(a, b, chunk=2, h0=8.0)
        np.testing.assert_allclose(np.asarray(h), ref_linear_scan(np.asarray(a), np.asarray(b), 8.0), rtol=1e-6)

    def test_h0_fold_with_numpy_inputs(self):
        # Regression: the old ``hasattr(b, "at")`` guard silently dropped h0
        # when a/b arrived as numpy arrays.
        rng = np.random.default_rng(3)
        a = rng.uniform(0.5, 1.0, (6, 2)).astype(np.float32)
        b = rng.standard_normal((6, 2)).astype(np.float32)
        h = linear_scan(a, b, h0=2.5)
        np.testing.assert_allclose(
            np.asarray(h), ref_linear_scan(a, b, 2.5), rtol=2e-5, atol=2e-5
        )
        # And identically for jax inputs (both paths share the fold now).
        h_jax = linear_scan(jnp.asarray(a), jnp.asarray(b), h0=2.5)
        np.testing.assert_allclose(np.asarray(h_jax), np.asarray(h), rtol=1e-6)


class TestSegmentMonoid:
    """The shared (decay, state) composition law behind chunked_linear_scan,
    device_linear_scan_carry and the WKV segment summaries."""

    def test_elementwise_compose_is_fold(self):
        rng = np.random.default_rng(7)
        segs = [(jnp.asarray(rng.uniform(0.5, 1.0, 3).astype(np.float32)),
                 jnp.asarray(rng.standard_normal(3).astype(np.float32)))
                for _ in range(4)]
        h0 = jnp.asarray(rng.standard_normal(3).astype(np.float32))
        composed = segs[0]
        for s in segs[1:]:
            composed = ELEMENTWISE.compose(composed, s)
        h = h0
        for s in segs:
            h = ELEMENTWISE.apply(s, h)
        np.testing.assert_allclose(
            np.asarray(ELEMENTWISE.apply(composed, h0)), np.asarray(h),
            rtol=1e-6)

    def test_diag_state_compose_is_fold(self):
        # The WKV case: (..., Dh) decay acting on the rows of a (Dh, Dh)
        # matrix state.
        rng = np.random.default_rng(8)
        dh = 4
        segs = [(jnp.asarray(rng.uniform(0.5, 1.0, dh).astype(np.float32)),
                 jnp.asarray(rng.standard_normal((dh, dh)).astype(np.float32)))
                for _ in range(3)]
        h0 = jnp.asarray(rng.standard_normal((dh, dh)).astype(np.float32))
        composed = segs[0]
        for s in segs[1:]:
            composed = DIAG_STATE.compose(composed, s)
        h = np.asarray(h0)
        for a, b_ in segs:
            h = np.asarray(a)[:, None] * h + np.asarray(b_)
        np.testing.assert_allclose(
            np.asarray(DIAG_STATE.apply(composed, h0)), h, rtol=1e-5,
            atol=1e-5)

    def test_chunked_linear_scan_diag_state(self):
        # chunked_linear_scan runs the matrix-state recurrence under the
        # same monoid: h_t = a_t[:, None] * h_{t-1} + b_t.
        rng = np.random.default_rng(9)
        t, dh = 8, 4
        a = rng.uniform(0.5, 1.0, (t, dh)).astype(np.float32)
        b = rng.standard_normal((t, dh, dh)).astype(np.float32)
        h0 = rng.standard_normal((dh, dh)).astype(np.float32)
        got = chunked_linear_scan(
            jnp.asarray(a), jnp.asarray(b), chunk=4, h0=h0,
            monoid=DIAG_STATE)
        ref = np.zeros((t, dh, dh), np.float32)
        prev = h0.copy()
        for i in range(t):
            prev = a[i][:, None] * prev + b[i]
            ref[i] = prev
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5,
                                   atol=2e-5)


def _mesh1d(n, name="x"):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices")
    return Mesh(np.array(devs[:n]), (name,))


@pytest.fixture(scope="module")
def mesh4():
    # Spawn extra host devices for this test module only via chex-free trick:
    # tests run under a separate pytest process; if only 1 device, skip.
    return _mesh1d(4)


class TestDeviceComm:
    """Device-space elevator tests run via shard_map on host devices.

    On the 1-device CPU container these exercise the n=1 path; the
    multi-device path is exercised by tests/test_multidevice.py which
    re-launches pytest with XLA_FLAGS=--xla_force_host_platform_device_count.
    """

    def test_device_shift_single(self):
        mesh = _mesh1d(1)
        f = shard_map(
            lambda x: device_shift(x, "x", delta=0, fill=0.0),
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        )
        x = jnp.arange(4.0)
        np.testing.assert_array_equal(f(x), x)

    def test_halo_noop(self):
        mesh = _mesh1d(1)
        f = shard_map(
            lambda x: halo_exchange(x, "x", left=0, right=0),
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        )
        x = jnp.arange(8.0)
        np.testing.assert_array_equal(f(x), x)


class TestDeviceCarryEdges:
    """Edge cases of the device-space carry sweeps.

    The single-device-axis cases run everywhere; the n=8 cases need the
    multi-device lane (scripts/tier1.sh lane 2, or any host with >= 8
    devices) — tests/test_multidevice.py covers them via subprocess too.
    """

    def test_single_device_axis_is_identity(self):
        # n=1: no predecessors — the entering carry is the monoid identity
        # (1, 0), forward and reverse.
        mesh = _mesh1d(1)
        for reverse in (False, True):
            f = shard_map(
                lambda a, b: device_linear_scan_carry(
                    a, b, "x", reverse=reverse),
                mesh=mesh, in_specs=(P("x"), P("x")), out_specs=(P("x"), P("x")),
            )
            ca, cb = f(jnp.full((1, 3), 0.5), jnp.ones((1, 3)))
            np.testing.assert_array_equal(np.asarray(ca), np.ones((1, 3)))
            np.testing.assert_array_equal(np.asarray(cb), np.zeros((1, 3)))

    def test_seq_carry_scan_single_device(self):
        # n=1: the chain degenerates to one chunk_fn call from carry_init,
        # in either direction.
        mesh = _mesh1d(1)
        x = jnp.arange(4.0)

        def chunk_fn(carry, v):
            return carry + v.sum(), v + carry

        for reverse in (False, True):
            def run(v, reverse=reverse):
                c, y = seq_carry_scan(
                    chunk_fn, jnp.asarray(10.0), v, "x", reverse=reverse)
                return c.reshape(1), y

            f = shard_map(run, mesh=mesh, in_specs=P("x"),
                          out_specs=(P("x"), P("x")))
            carry, y = f(x)
            np.testing.assert_allclose(np.asarray(carry), [16.0])
            np.testing.assert_allclose(np.asarray(y), np.arange(4.0) + 10.0)

    def test_carry_nonzero_h0_multidevice(self):
        # Nonzero h0 enters shard 0 as the boundary constant: the full
        # sharded scan with entering state ca*h0+cb matches the reference.
        mesh = _mesh1d(8)
        T, D = 32, 3
        rng = np.random.default_rng(11)
        a = rng.uniform(0.6, 1.0, (T, D)).astype(np.float32)
        b = rng.standard_normal((T, D)).astype(np.float32)
        h0 = rng.standard_normal(D).astype(np.float32)

        def sharded(a_loc, b_loc):
            h_loc = linear_scan(a_loc, b_loc)
            ca, cb = device_linear_scan_carry(
                jnp.prod(a_loc, axis=0), h_loc[-1], "x")
            enter = ca * h0 + cb
            return h_loc + jnp.cumprod(a_loc, axis=0) * enter[None]

        out = shard_map(sharded, mesh=mesh, in_specs=(P("x"), P("x")),
                        out_specs=P("x"))(jnp.asarray(a), jnp.asarray(b))
        ref = ref_linear_scan(a, b, h0)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=3e-4,
                                   atol=3e-4)

    def test_carry_reverse_multidevice(self):
        # reverse=True composes successor segments: the entering carry at
        # shard i equals the fold of shards n-1..i+1.
        mesh = _mesh1d(8)
        n, dh = 8, 3
        rng = np.random.default_rng(12)
        A = rng.uniform(0.5, 1.0, (n, dh)).astype(np.float32)
        B = rng.standard_normal((n, dh)).astype(np.float32)

        def rev(a, b):
            ca, cb = device_linear_scan_carry(a[0], b[0], "x", reverse=True)
            return ca[None], cb[None]

        ca, cb = shard_map(
            rev, mesh=mesh, in_specs=(P("x", None), P("x", None)),
            out_specs=(P("x", None), P("x", None)),
        )(jnp.asarray(A), jnp.asarray(B))
        prev_a = np.ones(dh, np.float32)
        prev_b = np.zeros(dh, np.float32)
        for i in range(n - 1, -1, -1):
            np.testing.assert_allclose(np.asarray(ca[i]), prev_a, rtol=1e-5)
            np.testing.assert_allclose(np.asarray(cb[i]), prev_b, rtol=1e-5,
                                       atol=1e-5)
            prev_a = A[i] * prev_a
            prev_b = A[i] * prev_b + B[i]
        # (update order: segment i applied after its successors)

    def test_seq_carry_scan_reverse_multidevice(self):
        mesh = _mesh1d(8)
        vals = jnp.arange(1.0, 9.0)

        def chunk_fn(carry, v):
            s = carry + v.sum()
            return s, jnp.zeros_like(v) + s

        def run(v):
            c, y = seq_carry_scan(
                chunk_fn, jnp.asarray(0.0), v, "x", reverse=True)
            return c.reshape(1), y

        carry, ys = shard_map(
            run, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P("x")))(vals)
        want = np.cumsum(np.arange(1.0, 9.0)[::-1])[::-1]
        np.testing.assert_allclose(np.asarray(ys), want, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(carry)[0], 36.0, rtol=1e-6)


class TestScratchpad:
    def test_barrier_identity(self):
        x = jnp.arange(4.0)
        np.testing.assert_array_equal(barrier(x), x)

    def test_shared_buffer_flow(self):
        buf = SharedBuffer((4,))
        buf.write(jnp.arange(4.0)).sync()
        np.testing.assert_array_equal(buf.read(), np.arange(4.0))
        assert buf.bytes_written == 16

    def test_read_before_sync_raises(self):
        buf = SharedBuffer((4,))
        buf.write(jnp.arange(4.0))
        with pytest.raises(RuntimeError):
            buf.read()
