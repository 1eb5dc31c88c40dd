"""SSD (Mamba-2) kernels against the sequential recurrence.

The Pallas kernels run in interpret mode on the CPU; the oracle is the
token-by-token scan ``ssd_sequential_ref``.  Shapes are small (8 heads,
the kernels' head block) but keep what the kernels must get right:
chunk boundaries, a sequence that is not a whole number of chunks, a
nonzero entering state, masked tokens (``dt = 0``) as identity steps and
decays from exp(-0.05) down to below exp(-20) a token.

Tolerances are relative to the reference's largest value: ``STATE_TOL``
for the f32 state (a state rounded to bf16 once a token fails it, as
``test_bf16_state_fails_the_tolerance`` shows), ``OUT_TOL`` for the bf16
output (its rounding moves a value by up to 2^-8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd import ops
from repro.kernels.ssd.decode import ssd_decode_window_pallas
from repro.kernels.ssd.kernel import ssd_chunk_pallas
from repro.kernels.ssd.ref import (random_inputs, rel_error, ssd_chunked_ref,
                                   ssd_sequential_ref)

jax.config.update("jax_platform_name", "cpu")

STATE_TOL = 1e-4
OUT_TOL = 2 ** -8
B, H, P, N = 2, 8, 16, 16


def inputs(t, seed=0, dtype=jnp.bfloat16):
    return random_inputs((B, H, P, N), t, seed, dtype)


rel = rel_error


@pytest.mark.parametrize("t", [64, 200])
def test_chunk_kernel_matches_sequential(t):
    args = inputs(t)
    y, s = ssd_chunk_pallas(*args[:5], args[5][None], 0, chunk=64,
                            interpret=True)
    y0, s0 = ssd_sequential_ref(*args)
    assert y.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    assert rel(y, y0) <= OUT_TOL
    assert rel(s[0], s0) <= STATE_TOL


def test_chunk_kernel_updates_its_layer_in_place():
    """Two and a half chunks into layer 1 of a three-layer stack."""
    args = inputs(160, seed=1)
    stack = jnp.stack([args[5] + 7.0, args[5], args[5] - 3.0])
    y, out = ssd_chunk_pallas(*args[:5], stack, 1, chunk=64, interpret=True)
    y0, s0 = ssd_sequential_ref(*args)
    assert rel(y, y0) <= OUT_TOL
    assert rel(out[1], s0) <= STATE_TOL
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(stack[0]))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(stack[2]))


def test_chunked_ref_matches_sequential():
    args = inputs(200, dtype=jnp.float32)
    y, s = ssd_chunked_ref(*args, chunk=64)
    y0, s0 = ssd_sequential_ref(*args)
    assert rel(y, y0) <= STATE_TOL and rel(s, s0) <= STATE_TOL


@pytest.mark.parametrize("t", [1, 5])
def test_decode_window_kernel_updates_its_layer_in_place(t):
    args = inputs(t, seed=1)
    stack = jnp.stack([args[5] + 7.0, args[5], args[5] - 3.0])
    y, out = ssd_decode_window_pallas(*args[:5], stack, 1, interpret=True)
    y0, s0 = ssd_sequential_ref(*args)
    assert rel(y, y0) <= OUT_TOL
    assert rel(out[1], s0) <= STATE_TOL
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(stack[0]))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(stack[2]))


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("t", [4, 150])
def test_masked_tokens_are_identity_steps(use_kernel, t):
    """Row 1 has two real tokens, then ``dt = 0``: its state is the
    state after those two tokens, whatever the masked tokens hold."""
    x, dt, a, bm, cm, h0 = inputs(t, seed=2)
    dt = dt.at[1, 2:].set(0.0)
    _, s = ops.ssd(x, dt, a, bm, cm, h0, decode=True, use_kernel=use_kernel)
    _, s2 = ssd_sequential_ref(x[:, :2], dt[:, :2], a, bm[:, :2], cm[:, :2], h0)
    assert rel(s[1], s2[1]) <= STATE_TOL


@pytest.mark.parametrize("use_kernel", [False, True])
def test_all_masked_row_state_is_bit_identical(use_kernel):
    x, dt, a, bm, cm, h0 = inputs(4, seed=3)
    dt = dt.at[0].set(0.0)
    _, s = ops.ssd(x, dt, a, bm, cm, h0, decode=True, use_kernel=use_kernel)
    np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(h0[0]))


def test_window_and_chunk_dispatch_agree():
    args = inputs(40, seed=4)
    yw, sw = ops.ssd(*args, decode=True, use_kernel=True)      # window
    yc, sc = ops.ssd(*args, decode=False, use_kernel=True)     # chunk
    assert rel(yw, yc.astype(jnp.float32)) <= 2 * OUT_TOL
    assert rel(sw, sc) <= STATE_TOL


def test_bf16_state_fails_the_tolerance():
    """The state tolerance tells an f32 state from one kept in bf16."""
    x, dt, a, bm, cm, h0 = inputs(200, seed=5)

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = (jnp.exp(a * dtt)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return s.astype(jnp.bfloat16).astype(jnp.float32), None

    xs = tuple(jnp.moveaxis(v.astype(jnp.float32), 1, 0)
               for v in (x, dt, bm, cm))
    s16, _ = jax.lax.scan(step, h0, xs)
    _, s0 = ssd_sequential_ref(x, dt, a, bm, cm, h0)
    assert rel(s16, s0) > STATE_TOL
