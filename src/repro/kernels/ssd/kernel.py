"""Pallas TPU kernel: chunked SSD (Mamba-2) prefill, every head's state in
VMEM across chunks.

Grid ``(batch, head_blocks, chunks)``, chunks fastest (the schedule of
:mod:`repro.kernels.wkv.kernel`): a ``(head_block, P, N)`` VMEM scratch
holds the state of the block's heads from chunk to chunk, seeded at
chunk 0 (:func:`repro.kernels.common.reset_carry`) and written to HBM
once.  The state is one layer of a stack ``(L, Bt, H, P, N)``, picked by
a prefetched layer index and written back in place, as the decode-window
kernel's is (:mod:`repro.kernels.ssd.decode`).  Per chunk of L tokens
and per head, all on
the MXU in f32 (``Precision.HIGHEST``):

    y     = ((C B^T) * exp(cs_t - cs_s) [s <= t]) (dt x)
            + exp(cs_t) (C S^T)                       (the entering state)
    S_out = exp(cs_L) S + (dt x * exp(cs_L - cs))^T B

where ``cs`` is the in-chunk cumulative log-decay (``dt * A``).  Every
exponent is <= 0 (SSD's segment sums), so decays of any size are exact
up to rounding; the WKV kernel's decay-ratio trick would overflow.
``C B^T`` is shared by all heads (one group) and computed once a step.

The wrapper pads T to whole chunks with identity steps (``dt = 0``) and
lays the operands out for the tiling: x and y ``(Bt, H, T, P)``, the
per-head columns dt and cs ``(Bt, H/hb, T, hb)``, cs again as rows
``(Bt, H, T)`` for the ``(L, L)`` segment sums.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import reset_carry
from repro.kernels.ssd.ref import chunk_cumsum, pad_time

#: Tokens per chunk: the lane width, so the (L, L) segment sums and the
#: per-head cumulative-decay rows tile exactly.
CHUNK = 128
#: Heads per grid step (a multiple of 8: the rows of the cs block).
HEAD_BLOCK = 8

F32 = jnp.float32
HP = jax.lax.Precision.HIGHEST


def ssd_chunk_kernel(layer_ref, x_ref, dt_ref, csc_ref, csr_ref, b_ref, c_ref,
                     h0_ref, y_ref, s_out_ref, s_ref, *, hb: int, chunk: int):
    del layer_ref                                         # in the index maps
    reset_carry(s_ref, h0_ref[0, 0], seq_axis=2)
    bc = b_ref[0]                                         # (L, N)
    cc = c_ref[0]
    g = jax.lax.dot_general(cc, bc, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32)  # (L, L) = C B^T
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = si <= ti
    bf, cf = bc.astype(F32), cc.astype(F32)
    n = bf.shape[1]
    is_last = jax.lax.broadcasted_iota(jnp.int32, (chunk, n), 0) == chunk - 1

    def last_row(cs):
        # cs[L-1] as a (1, N) row: Mosaic cannot broadcast a (1, 1) value
        # along sublanes and lanes at once, so it is picked out of the
        # lane-broadcast column by a sublane sum.
        return jnp.sum(jnp.where(is_last, jnp.broadcast_to(cs, (chunk, n)),
                                 0.0), axis=0, keepdims=True)

    for j in range(hb):
        cs = csc_ref[0, 0, :, j:j + 1]                    # (L, 1)
        last = cs[chunk - 1:chunk]                        # (1, 1)
        seg = jnp.where(causal, cs - csr_ref[0, j:j + 1, :], -jnp.inf)
        m = g * jnp.exp(seg)                              # (L, L)
        v = dt_ref[0, 0, :, j:j + 1] * x_ref[0, j].astype(F32)   # (L, P)
        S = s_ref[j]                                      # (P, N)
        y = jnp.dot(m, v, precision=HP, preferred_element_type=F32)
        y = y + jnp.exp(cs) * jax.lax.dot_general(
            cf, S, (((1,), (1,)), ((), ())), precision=HP,
            preferred_element_type=F32)
        y_ref[0, j] = y.astype(y_ref.dtype)
        w = v * jnp.exp(last - cs)
        S = jnp.exp(last_row(cs)) * S + jax.lax.dot_general(
            w, bf, (((0,), (0,)), ((), ())), precision=HP,
            preferred_element_type=F32)
        s_ref[j] = S
        s_out_ref[0, 0, j] = S                            # last chunk wins


def head_block(h: int) -> int:
    hb = min(HEAD_BLOCK, h)
    if h % hb or (hb % 8 and hb != h):
        raise ValueError(f"SSD heads {h}: no head block of at most "
                         f"{HEAD_BLOCK} that is a multiple of 8 divides them")
    return hb


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_pallas(x, dt, A, Bm, Cm, h, layer, *, chunk: int = CHUNK,
                     interpret: bool = False):
    """Chunked SSD over layer ``layer`` of the state stack ``h`` (L, Bt,
    H, P, N) f32.  x (Bt, T, H, P); dt (Bt, T, H) f32; A (H,) f32; Bm, Cm
    (Bt, T, N).  Returns (y (Bt, T, H, P) in x.dtype, the stack with that
    layer updated)."""
    b, t, nh, p = x.shape
    n = Bm.shape[-1]
    hb = head_block(nh)
    pad = pad_time(t, chunk)
    tp = t + pad
    padt = lambda a: jnp.pad(a, [(0, 0), (0, pad)]  # noqa: E731
                             + [(0, 0)] * (a.ndim - 2)) if pad else a
    x, dt, Bm, Cm = padt(x), padt(dt.astype(F32)), padt(Bm), padt(Cm)
    cs = chunk_cumsum(dt * A.astype(F32), chunk)          # (Bt, Tp, H)
    cols = lambda a: a.reshape(b, tp, nh // hb, hb).transpose(0, 2, 1, 3)  # noqa: E731
    seq = pl.BlockSpec((1, hb, chunk, p), lambda bi, hi, si, _: (bi, hi, si, 0))
    col = pl.BlockSpec((1, 1, chunk, hb), lambda bi, hi, si, _: (bi, hi, si, 0))
    row = pl.BlockSpec((1, hb, chunk), lambda bi, hi, si, _: (bi, hi, si))
    bcs = pl.BlockSpec((1, chunk, n), lambda bi, hi, si, _: (bi, si, 0))
    state = pl.BlockSpec((1, 1, hb, p, n),
                         lambda bi, hi, si, lr: (lr[0], bi, hi, 0, 0))
    y, h = pl.pallas_call(
        functools.partial(ssd_chunk_kernel, hb=hb, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nh // hb, tp // chunk),
            in_specs=[seq, col, col, row, bcs, bcs, state],
            out_specs=(seq, state),
            scratch_shapes=[pltpu.VMEM((hb, p, n), F32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((b, nh, tp, p), x.dtype),
                   jax.ShapeDtypeStruct(h.shape, F32)),
        input_output_aliases={7: 1},
        interpret=interpret,
        name="ssd_chunk_pallas",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), x.transpose(0, 2, 1, 3),
      cols(dt), cols(cs), cs.transpose(0, 2, 1), Bm, Cm, h)
    return y[:, :, :t].transpose(0, 2, 1, 3), h
