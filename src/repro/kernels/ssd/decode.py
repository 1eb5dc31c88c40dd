"""Pallas TPU decode-window kernel: the SSD state in VMEM across a
window's tokens.

Grid ``(batch, head_blocks)``: the whole window is one block and a loop
inside the kernel walks its K tokens, carrying each head's (P, N) state
from token to token in f32 on the VPU (the sequential form, exact: no
chunk structure, no exponent bookkeeping).  HBM sees the state read once
and written once per window; K = 1 is a decode step.  The state is one
layer of a stack ``(L, Bt, H, P, N)``, picked by a prefetched layer
index and written back in place (the output aliases the stack), so the
layer scan of a decode step moves each layer's state through HBM once
and copies nothing around the kernel.
Per token, with B and C shared by every head:

    S = exp(A dt) S + (dt x) B^T,    y = S C

Layouts put the token axis on lanes, so a token is a static lane slice:
x and y ``(Bt, H, P, K)`` (P on sublanes, for the outer product with the
lane row ``B``), dt and the log-decay ``(Bt, H, 1, K)``.  Dispatch sends
windows up to :data:`repro.kernels.wkv.decode.DECODE_WINDOW_MAX` tokens
here, longer ones to the chunk kernel (:mod:`repro.kernels.ssd.kernel`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ssd.kernel import head_block
from repro.kernels.wkv.decode import DECODE_WINDOW_MAX

F32 = jnp.float32


def ssd_decode_window_kernel(layer_ref, x_ref, dt_ref, la_ref, b_ref, c_ref,
                             h_ref, y_ref, s_out_ref):
    del layer_ref                                         # in the index maps
    S = h_ref[0, 0]                                       # (hb, P, N)
    # Static unroll (K <= DECODE_WINDOW_MAX): token i is lane i.
    for i in range(x_ref.shape[-1]):
        tok = slice(i, i + 1)
        v = dt_ref[0, :, :, tok] * x_ref[0, :, :, tok]    # (hb, P, 1)
        a = jnp.exp(la_ref[0, :, :, tok])                 # (hb, 1, 1)
        b = b_ref[0, tok, :].astype(F32)[None]            # (1, 1, N)
        c = c_ref[0, tok, :].astype(F32)[None]
        S = a * S + v * b
        y_ref[0, :, :, tok] = jnp.sum(S * c, axis=-1, keepdims=True)
    s_out_ref[0, 0] = S


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode_window_pallas(x, dt, A, Bm, Cm, h, layer, *,
                             interpret: bool = False):
    """K-token decode window, 1 <= K <= DECODE_WINDOW_MAX, over layer
    ``layer`` of the state stack ``h`` (L, Bt, H, P, N) f32.  x (Bt, K, H,
    P); dt (Bt, K, H) f32; A (H,) f32; Bm, Cm (Bt, K, N).  Returns (y
    (Bt, K, H, P) in x.dtype, the stack with that layer updated)."""
    b, t, nh, p = x.shape
    n = Bm.shape[-1]
    if t > DECODE_WINDOW_MAX:
        raise ValueError(f"decode window K={t} > {DECODE_WINDOW_MAX}: the "
                         f"kernel unrolls K, longer sweeps take the chunk "
                         f"kernel")
    hb = head_block(nh)
    dt = dt.astype(F32)
    lanes = lambda a: a.transpose(0, 2, 1)[:, :, None, :]  # noqa: E731
    seq = pl.BlockSpec((1, hb, p, t), lambda bi, hi, _: (bi, hi, 0, 0))
    tok = pl.BlockSpec((1, hb, 1, t), lambda bi, hi, _: (bi, hi, 0, 0))
    bcs = pl.BlockSpec((1, t, n), lambda bi, hi, _: (bi, 0, 0))
    state = pl.BlockSpec((1, 1, hb, p, n),
                         lambda bi, hi, lr: (lr[0], bi, hi, 0, 0))
    y, h = pl.pallas_call(
        ssd_decode_window_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, x.shape[2] // hb),
            in_specs=[seq, tok, tok, bcs, bcs, state],
            out_specs=(seq, state),
        ),
        out_shape=(jax.ShapeDtypeStruct((b, x.shape[2], p, t), F32),
                   jax.ShapeDtypeStruct(h.shape, F32)),
        input_output_aliases={6: 1},
        interpret=interpret,
        name="ssd_decode_window_pallas",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      x.astype(F32).transpose(0, 2, 3, 1), lanes(dt),
      lanes(dt * A.astype(F32)), Bm, Cm, h)
    return y.transpose(0, 3, 1, 2).astype(x.dtype), h
