"""Pallas TPU decode-window kernel: the SSD state in VMEM across a
window's tokens, for the live rows only.

Grid ``(batch, H / row_block)``: a step moves one row's block of
``row_block`` heads' f32 states (every head of the row where the VMEM
budget allows, :func:`row_block`) and walks the window's K tokens, each
head's (P, N) state updated in turn on the VPU in f32 (the sequential
form, exact: no chunk structure, no exponent bookkeeping).  The state is
one layer of a stack ``(L, Bt, H, P, N)``, picked by a prefetched layer
index and written back in place (the output aliases the stack), so the
layer scan of a decode step moves each layer's state through HBM once
and copies nothing around the kernel.  A K-token call reads and writes a
live row's state once; a served window runs K = 1 calls, one a token, so
there the state crosses HBM once each way a token.  Per token, with B
and C shared by every head:

    S = exp(A dt) S + (dt x) B^T,    y = S C

Live rows: a prefetched list of the rows that hold a valid token, live
rows first, and their count.  Grid step ``i`` visits the i-th live row;
the steps past the count map to the last live row's last block, so
Pallas makes no DMA and no write-back for them, and their body is
skipped.  A row with no valid token is neither read nor written (its
state stays bit-identical, NaN included) and its ``y`` is zero.  With no
live row at all the one block the grid maps to is copied through VMEM
unchanged.

Layouts put P on sublanes and the heads on lanes, so a K = 1 call moves
one (P, H) tile of x and of y a row and no lane-padded slivers, and a
head is a static lane slice of it: x and y ``(Bt, K, P, H)``, dt and the
log-decay ``dt A`` ``(Bt, K, 1, H)``, B and C ``(Bt, K, 1, N)``.
Dispatch sends windows up to
:data:`repro.kernels.wkv.decode.DECODE_WINDOW_MAX` tokens here, longer
ones to the chunk kernel (:mod:`repro.kernels.ssd.kernel`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.wkv.decode import DECODE_WINDOW_MAX

F32 = jnp.float32
#: VMEM a grid step's blocks may take, double-buffered: the row block is
#: the most heads of a row whose blocks fit.
VMEM_BUDGET = 32 * 2**20


def _pad(d: int, to: int) -> int:
    return -(-d // to) * to


def step_bytes(hb: int, k: int, p: int, n: int) -> int:
    """VMEM of one grid step's blocks over ``hb`` heads and ``k`` tokens,
    each double-buffered: the f32 state in and out; x and y, a (P, hb)
    tile a token; dt, dt A, B and C, a row a token (counted at f32)."""
    state = hb * _pad(p, 8) * _pad(n, 128) * 4
    tile = k * _pad(p, 8) * _pad(hb, 128) * 4
    row = k * 8 * _pad(max(hb, n), 128) * 4
    return 2 * (2 * state + 2 * tile + 4 * row)


def row_block(h: int, k: int, p: int, n: int) -> int:
    """Heads per grid step: all ``h`` where their blocks fit
    :data:`VMEM_BUDGET`, else the largest multiple of 128 lanes that
    divides ``h`` and fits (``h`` when none does)."""
    fits = [hb for hb in range(128, h, 128)
            if h % hb == 0 and step_bytes(hb, k, p, n) <= VMEM_BUDGET]
    if step_bytes(h, k, p, n) <= VMEM_BUDGET or not fits:
        return h
    return max(fits)


def live_rows(live):
    """(rows, count): the indices of the rows with a valid token first,
    in order, then the others (Bt,) int32; how many are live (1,)."""
    return (jnp.argsort(~live, stable=True).astype(jnp.int32),
            jnp.sum(live, dtype=jnp.int32).reshape(1))


def live_block(i, j, rows, count, last: int):
    """Grid step (i, j) -> (row, head block): the i-th live row's j-th
    block.  Steps past ``count`` stay on the last live row's last block
    (``last``), so the block index repeats and Pallas makes no DMA and no
    write-back for them; with no live row every step maps to one
    block."""
    on = i < count
    return (rows[jnp.where(on, i, jnp.maximum(count - 1, 0))],
            jnp.where(on, j, last))


def ssd_decode_window_kernel(layer_ref, rows_ref, count_ref, x_ref, dt_ref,
                             la_ref, b_ref, c_ref, h_ref, y_ref, s_out_ref):
    del layer_ref, rows_ref                               # in the index maps
    count = count_ref[0]
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    def token(i, src):
        xt = x_ref[0, i].astype(F32)                      # (P, hb)
        v = dt_ref[0, i] * xt
        a = jnp.broadcast_to(jnp.exp(la_ref[0, i]), xt.shape)
        b = b_ref[0, i].astype(F32)                       # (1, N)
        c = c_ref[0, i].astype(F32)
        for j in range(h_ref.shape[2]):                   # static: lane j
            S = a[:, j:j + 1] * src[0, 0, j] + v[:, j:j + 1] * b
            s_out_ref[0, 0, j] = S
            y_ref[0, i, :, j:j + 1] = jnp.sum(S * c, axis=-1, keepdims=True)

    @pl.when(pl.program_id(0) < count)
    def _():
        token(0, h_ref)
        if x_ref.shape[1] > 1:
            def later(i, carry):
                token(i, s_out_ref)
                return carry

            jax.lax.fori_loop(1, x_ref.shape[1], later, 0)

    @pl.when((count == 0) & first)
    def _():
        s_out_ref[...] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode_window_pallas(x, dt, A, Bm, Cm, h, layer, live=None, *,
                             interpret: bool = False):
    """K-token decode window, 1 <= K <= DECODE_WINDOW_MAX, over layer
    ``layer`` of the state stack ``h`` (L, Bt, H, P, N) f32.  x (Bt, K, H,
    P); dt (Bt, K, H) f32; A (H,) f32; Bm, Cm (Bt, K, N); ``live`` (Bt,)
    bool, the rows with a valid token (all when None): the others are
    neither read nor written and their y is zero.  Returns (y (Bt, K, H,
    P) in x.dtype, the stack with that layer updated)."""
    b, t, nh, p = x.shape
    n = Bm.shape[-1]
    if t > DECODE_WINDOW_MAX:
        raise ValueError(f"decode window K={t} > {DECODE_WINDOW_MAX}: the "
                         f"kernel walks K tokens a block, longer sweeps "
                         f"take the chunk kernel")
    hb = row_block(nh, t, p, n)
    last = nh // hb - 1
    if live is None:
        live = jnp.ones((b,), bool)
    rows, count = live_rows(live)
    dt = dt.astype(F32)

    def block(bi, hi, rows_ref, count_ref):
        return live_block(bi, hi, rows_ref, count_ref[0], last)

    def lane_map(bi, hi, _, rows_ref, count_ref):
        r, hj = block(bi, hi, rows_ref, count_ref)
        return r, 0, 0, hj

    def row_map(bi, hi, _, rows_ref, count_ref):
        return block(bi, hi, rows_ref, count_ref)[0], 0, 0, 0

    def state_map(bi, hi, layer_ref, rows_ref, count_ref):
        r, hj = block(bi, hi, rows_ref, count_ref)
        return layer_ref[0], r, hj, 0, 0

    tile = pl.BlockSpec((1, t, p, hb), lane_map)
    heads = pl.BlockSpec((1, t, 1, hb), lane_map)
    bcs = pl.BlockSpec((1, t, 1, n), row_map)
    state = pl.BlockSpec((1, 1, hb, p, n), state_map)
    y, h = pl.pallas_call(
        ssd_decode_window_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nh // hb),
            in_specs=[tile, heads, heads, bcs, bcs, state],
            out_specs=(tile, state),
        ),
        out_shape=(jax.ShapeDtypeStruct((b, t, p, nh), F32),
                   jax.ShapeDtypeStruct(h.shape, F32)),
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=step_bytes(hb, t, p, n) + 4 * 2**20),
        interpret=interpret,
        name="ssd_decode_window_pallas",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows, count,
      x.transpose(0, 1, 3, 2), dt[:, :, None], (dt * A.astype(F32))[:, :, None],
      Bm[:, :, None], Cm[:, :, None], h)
    y = jnp.where(live[:, None, None, None], y.transpose(0, 1, 3, 2), 0)
    return y.astype(x.dtype), h
