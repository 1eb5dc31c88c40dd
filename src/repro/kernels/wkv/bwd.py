"""Pallas TPU kernel: reverse elevator sweep for the fused WKV backward.

The training-loop twin of :mod:`repro.kernels.wkv.kernel`.  The heaviest
loop-carried value of the backward pass is the adjoint state ``dS`` — a
(Dh × Dh) matrix per (batch, head) flowing from chunk ``s+1`` to chunk
``s``.  This kernel carries it exactly the way the forward carries ``S``,
with the sweep direction reversed:

* same ``(batch, head, seq_chunks)`` grid, but the block index maps walk
  the sequence axis back-to-front (:func:`repro.kernels.common.reversed_chunk`)
  — a Δ=-1 elevator edge over chunk space;
* the ``pltpu.VMEM((dh, dh))`` scratch is the adjoint token buffer, reset
  at the *last* chunk (grid step 0 of the reversed sweep) to the incoming
  state cotangent ``dS_out`` — the reverse ``fromThreadOrConst`` boundary;
* **recompute over stage**: the per-chunk decay tensors (cumulative
  log-decays, ``r_dec``/``k_inv``/``k_rem``) and the masked score matrix
  are recomputed from the primal inputs inside the kernel — in-fabric VPU
  work — instead of being saved by the forward and round-tripped through
  HBM the way ``jax.grad`` of the chunked reference stages them.  The one
  staged residual is ``s_hist`` (the state entering each chunk, N small
  (Dh × Dh) tokens), because it flows *forward* and cannot be produced by
  a backward sweep;
* the adjoint of a forward prefix-sum (the cumulative log-decay chains) is
  a *suffix* sum — :func:`repro.kernels.common.rev_cumsum_rows`, the same
  Hillis–Steele forwarding network run with negative shifts.

Per chunk (length L, entering state S, exit-state adjoint G = scratch):

    dr_dec = dscores @ k_inv + do @ S^T          dscores = mask(do @ V^T)
    dk     = (dscores^T r_dec) ⊙ e^{-cum} + (V G^T) ⊙ e^{cum[-1]-cum} + bonus
    dv     = scores^T do + k_rem G + bonus
    dlogw  = rev_cumsum(dcum_incl) + rev_cumsum_excl(dcum_excl)
    G_prev = diag(w_total) G + r_dec^T do        (the carried token)

``du`` accumulates per (batch, head) tile in a VMEM scratch and is summed
over batch outside; ``dh0`` is the carry after chunk 0 (last grid step).

The same reversal exists one level up: under sequence sharding
(``seqpar.py``) the ``dS`` emitted here as ``dh0`` becomes a shard's
exit-state adjoint, and the device-space carry composition transposes
into reverse-direction ppermute hops — this kernel is the in-chip leg of
that sweep, the ICI hops are its between-chip continuation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    cumsum_rows,
    head_row_spec,
    reset_carry,
    rev_cumsum_rows,
    reversed_chunk,
    validate_divisible,
)


def wkv_bwd_kernel(
    r_ref, k_ref, v_ref, w_ref, u_ref, s_hist_ref, do_ref, ds_out_ref,
    dr_ref, dk_ref, dv_ref, dw_ref, du_ref, dh0_ref,
    ds_ref, du_acc_ref,
    *, chunk: int,
):
    # Reverse boundary: the last chunk (grid step 0) withdraws the output
    # state cotangent instead of a successor token; du starts at zero.
    reset_carry(ds_ref, ds_out_ref[0, 0], seq_axis=2)
    reset_carry(du_acc_ref, seq_axis=2)

    dh = r_ref.shape[-1]
    r = r_ref[0, 0].astype(jnp.float32)        # (chunk, dh)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)           # (1, dh)
    do = do_ref[0, 0].astype(jnp.float32)      # (chunk, dh)
    S = s_hist_ref[0, 0, 0]                    # (dh, dh) entering state
    dS = ds_ref[...]                           # (dh, dh) exit-state adjoint

    # Recomputed decays — identical math to the forward kernel, in-fabric.
    logw = jnp.log(jnp.clip(w, 1e-8, 1.0))
    cum_incl = cumsum_rows(logw, chunk)
    cum_excl = cum_incl - logw
    cum_last = cum_incl[chunk - 1:]            # (1, dh)
    w_total = jnp.exp(cum_last)                # (1, dh)
    r_dec = r * jnp.exp(cum_excl)
    k_inv = k * jnp.exp(-cum_incl)
    k_rem = k * jnp.exp(cum_last - cum_incl)

    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = si < ti
    scores = jnp.where(lower, jax.lax.dot_general(
        r_dec, k_inv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ), 0.0)
    dscores = jnp.where(lower, jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ), 0.0)

    dov = jnp.sum(do * v, axis=1, keepdims=True)            # (chunk, 1)

    # Adjoints of the decay-weighted operands.
    d_rdec = jnp.dot(dscores, k_inv, preferred_element_type=jnp.float32) + \
        jax.lax.dot_general(do, S, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    d_kinv = jax.lax.dot_general(
        dscores, r_dec, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    d_krem = jax.lax.dot_general(
        v, dS, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    dr = d_rdec * jnp.exp(cum_excl) + u * k * dov
    dk = (d_kinv * jnp.exp(-cum_incl)
          + d_krem * jnp.exp(cum_last - cum_incl)
          + r * u * dov)
    dv = (jax.lax.dot_general(scores, do, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
          + jnp.dot(k_rem, dS, preferred_element_type=jnp.float32)
          + jnp.sum(r * u * k, axis=1, keepdims=True) * do)

    # logw adjoint: suffix sums (adjoint of the forward prefix sums), with
    # the cum_last consumers (k_rem numerator, w_total in the exit
    # decay) folded onto the last row first.
    dcum_excl = d_rdec * r_dec
    dcum_incl = -d_kinv * k_inv - d_krem * k_rem
    last = (jnp.sum(d_krem * k_rem, axis=0, keepdims=True)
            + w_total * jnp.sum(S * dS, axis=1, keepdims=True).T)  # (1, dh)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, dh), 0)
    dcum_incl = dcum_incl + jnp.where(row == chunk - 1, last, 0.0)
    dlogw = (rev_cumsum_rows(dcum_incl, chunk)
             + rev_cumsum_rows(dcum_excl, chunk) - dcum_excl)
    in_range = (w >= 1e-8) & (w <= 1.0)
    dw = jnp.where(in_range, dlogw / jnp.clip(w, 1e-8, 1.0), 0.0)

    dr_ref[0, 0] = dr.astype(dr_ref.dtype)
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)
    dw_ref[0, 0] = dw.astype(dw_ref.dtype)

    # du partial: accumulate over this (batch, head) tile's chunks.
    du_acc_ref[...] = du_acc_ref[...] + jnp.sum(r * k * dov, axis=0,
                                                keepdims=True)
    du_ref[0, 0] = du_acc_ref[...]             # last grid step wins

    # Adjoint token hand-off (retag TID -> TID - 1): the entering-state
    # adjoint becomes the predecessor chunk's exit-state adjoint.
    dS_prev = dS * w_total.T + jax.lax.dot_general(
        r_dec, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds_ref[...] = dS_prev
    dh0_ref[0, 0] = dS_prev                    # last grid step = chunk 0


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas_bwd(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    s_hist: jax.Array,
    d_out: jax.Array,
    d_s_out: jax.Array,
    *,
    chunk: int = 64,
    interpret: bool = False,
):
    """Reverse chunk sweep.  r/k/v/w/d_out: (B, H, T, Dh); u: (H, Dh);
    s_hist: (B, H, N, Dh, Dh) chunk-entry states from the training forward;
    d_s_out: (B, H, Dh, Dh).

    Returns ``(dr, dk, dv, dw, du_part, dh0)`` — dr/dk/dv/dw in the primal
    dtypes, ``du_part`` (B, H, Dh) per-batch partials (sum over batch for
    the u cotangent), ``dh0`` (B, H, Dh, Dh) float32.
    """
    b, h, t, dh = r.shape
    validate_divisible("T", t, chunk)
    n_chunks = t // chunk
    if s_hist.shape != (b, h, n_chunks, dh, dh):
        raise ValueError(
            f"s_hist shape {s_hist.shape} != {(b, h, n_chunks, dh, dh)}"
        )

    grid = (b, h, n_chunks)
    rev = reversed_chunk(n_chunks)
    rev_seq = pl.BlockSpec(
        (1, 1, chunk, dh), lambda bi, hi, si: (bi, hi, rev(si), 0)
    )
    rev_hist = pl.BlockSpec(
        (1, 1, 1, dh, dh), lambda bi, hi, si: (bi, hi, rev(si), 0, 0)
    )
    state_spec = pl.BlockSpec((1, 1, dh, dh), lambda bi, hi, si: (bi, hi, 0, 0))
    kernel = functools.partial(wkv_bwd_kernel, chunk=chunk)
    dr, dk, dv, dw, du, dh0 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            rev_seq,   # r
            rev_seq,   # k
            rev_seq,   # v
            rev_seq,   # w
            head_row_spec(dh),  # u
            rev_hist,  # s_hist (entry state per chunk)
            rev_seq,   # d_out
            state_spec,  # d_s_out (reverse boundary constant)
        ],
        out_specs=(
            rev_seq,   # dr
            rev_seq,   # dk
            rev_seq,   # dv
            rev_seq,   # dw
            # du as (B, H, 1, Dh): a (1, Dh) trailing block equals the
            # array's, where (1, 1, Dh) over (B, H, Dh) does not tile.
            pl.BlockSpec((1, 1, 1, dh), lambda bi, hi, si: (bi, hi, 0, 0)),
            state_spec,  # dh0
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, t, dh), r.dtype),
            jax.ShapeDtypeStruct((b, h, t, dh), k.dtype),
            jax.ShapeDtypeStruct((b, h, t, dh), v.dtype),
            jax.ShapeDtypeStruct((b, h, t, dh), w.dtype),
            jax.ShapeDtypeStruct((b, h, 1, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, h, dh, dh), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),  # dS adjoint carry
            pltpu.VMEM((1, dh), jnp.float32),   # du accumulator
        ],
        interpret=interpret,
    )(r, k, v, w, u.reshape(h, 1, dh), s_hist, d_out, d_s_out)
    return dr, dk, dv, dw, du[:, :, 0], dh0
