"""SSD (Mamba-2) kernels against the sequential recurrence.

The Pallas kernels run in interpret mode on the CPU; the oracle is the
token-by-token scan ``ssd_sequential_ref``.  Shapes are small (8 heads,
the chunk kernel's head block) but keep what the kernels must get right:
chunk boundaries, a sequence that is not a whole number of chunks, a
nonzero entering state, masked tokens (``dt = 0``) as identity steps,
decays from exp(-0.05) down to below exp(-20) a token, and the decode
window's live rows: a row with no valid token is neither read nor
written (its state bit for bit, NaN included) and its y is zero.

Tolerances are relative to the reference's largest value: ``STATE_TOL``
for the f32 state (a state rounded to bf16 once a token fails it, as
``test_bf16_state_fails_the_tolerance`` shows), ``OUT_TOL`` for the bf16
output (its rounding moves a value by up to 2^-8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd import decode, ops
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


#: Live rows of a 32-row batch.
LIVE = {"all": range(32), "none": (), "rows_0_5_31": (0, 5, 31),
        "one": (12,)}


def bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def check_live_rows(live, t, heads=H, p=P):
    """Layer 1 of a three-layer stack over 32 rows, ``live`` of them
    live: live rows against the sequential scan; dead rows (one seeded
    with NaN, all with nonzero dt) bit for bit, their y zero; the NaN in
    no live row; the other layers untouched."""
    x, dt, a, bm, cm, h0 = random_inputs((32, heads, p, N), t, seed=6)
    dead = np.flatnonzero(~live)
    if dead.size:
        h0 = h0.at[dead[0], 0, 0, 0].set(jnp.nan)
    stack = jnp.stack([h0 + 7.0, h0, h0 - 3.0])
    y, out = ssd_decode_window_pallas(x, dt, a, bm, cm, stack, 1,
                                      jnp.asarray(live), interpret=True)
    if live.any():
        y0, s0 = ssd_sequential_ref(x[live], dt[live], a, bm[live], cm[live],
                                    h0[live])
        assert rel(y[live], y0) <= OUT_TOL
        assert rel(out[1][live], s0) <= STATE_TOL
        assert np.all(np.isfinite(np.asarray(out[1][live])))
    np.testing.assert_array_equal(bits(out[1][~live]), bits(h0[~live]))
    assert not np.any(np.asarray(y[~live], np.float32))
    np.testing.assert_array_equal(bits(out[::2]), bits(stack[::2]))


@pytest.mark.parametrize("t", [1, 8, 64])
@pytest.mark.parametrize("pattern", list(LIVE))
def test_decode_window_walks_only_live_rows(pattern, t):
    check_live_rows(np.isin(np.arange(32), LIVE[pattern]), t)


@pytest.mark.parametrize("blocks", [1, 3])
@pytest.mark.parametrize("pattern", list(LIVE))
def test_grid_visits_only_live_rows(pattern, blocks):
    """The index maps walk the live rows' head blocks in order, then
    repeat the last one, for which Pallas moves nothing: no dead row's
    block is visited.  With no live row every step maps to one block,
    which the kernel copies through unchanged."""
    live = np.isin(np.arange(32), LIVE[pattern])
    rows, count = decode.live_rows(jnp.asarray(live))
    seq = [tuple(int(v) for v in decode.live_block(
        i, j, rows, count[0], blocks - 1))
        for i in range(32) for j in range(blocks)]
    walked = [(r, j) for r in np.flatnonzero(live) for j in range(blocks)]
    assert seq[:len(walked)] == walked
    assert set(seq[len(walked):]) <= {(walked or [(0, blocks - 1)])[-1]}


@pytest.fixture
def two_head_blocks(monkeypatch):
    """A VMEM budget that fits 128 heads a step, not 256: a row of 256
    heads takes two grid steps."""
    monkeypatch.setattr(decode, "VMEM_BUDGET",
                        decode.step_bytes(128, 1, 8, N))
    assert decode.row_block(256, 1, 8, N) == 128
    ssd_decode_window_pallas.clear_cache()
    yield
    ssd_decode_window_pallas.clear_cache()


@pytest.mark.parametrize("pattern", list(LIVE))
def test_decode_window_live_rows_over_head_blocks(pattern, two_head_blocks):
    """Steps past the live count stay on the last live row's last head
    block, and a row's blocks are walked in turn."""
    check_live_rows(np.isin(np.arange(32), LIVE[pattern]), 1, heads=256,
                    p=8)


@pytest.mark.parametrize("t", [1, 8, 64])
def test_granite_row_is_one_grid_step(t):
    """At granite's widths (64 heads of 64, d_state 128) a row's whole
    state is one block for every window length, within the budget."""
    assert decode.row_block(64, t, 64, 128) == 64
    assert decode.step_bytes(64, t, 64, 128) <= decode.VMEM_BUDGET


@pytest.mark.parametrize("use_kernel", [False, True])
def test_ops_live_rows(use_kernel):
    """Through the dispatch: rows whose tokens are all masked (dt = 0)
    keep their state bit for bit, on the oracle (identity steps) and the
    kernel (not walked); live rows' y agree."""
    x, dt, a, bm, cm, h0 = inputs(3, seed=7)
    live = jnp.asarray([False, True])
    dt = jnp.where(live[:, None, None], dt, 0.0)
    y, s = ops.ssd(x, dt, a, bm, cm, h0, live=live, decode=True,
                   use_kernel=use_kernel)
    y0, s0 = ssd_sequential_ref(x, dt, a, bm, cm, h0)
    np.testing.assert_array_equal(bits(s[0]), bits(h0[0]))
    assert rel(y[1], y0[1]) <= OUT_TOL and rel(s[1], s0[1]) <= STATE_TOL


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
