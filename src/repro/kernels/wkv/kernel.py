"""Pallas TPU kernel: fused RWKV6 WKV recurrence, matrix state in VMEM.

The heaviest loop-carried value in this repo is the WKV state — a
(Dh × Dh) matrix per (batch, head) forwarded from chunk to chunk.  This
kernel is the paper's §4.3 construction applied to it:

* the ``pltpu.VMEM((dh, dh))`` scratch is the elevator *token buffer* of a
  Δ=1 edge over chunk space: chunk ``s`` deposits its exit state, chunk
  ``s+1`` (the next grid step on the same (batch, head) tile) withdraws it
  — a point-to-point hand-off that never touches HBM, where the jnp
  fallback's ``lax.scan`` carry round-trips every chunk (Fig. 1b);
* ``h0`` is the boundary constant ``C`` of ``fromThreadOrConst``: chunk 0
  withdraws it instead of a predecessor token;
* the per-chunk decay tensors (``r_dec``, ``k_inv``, ``k_rem``, cumulative
  log-decays) and the masked score matrix are fused into the same pass —
  in-fabric values on the VPU/MXU, never materialized.

Grid: ``(batch, head, seq_chunks)``, sequence fastest, so the scratch is
private per (batch, head) and reset at chunk 0 — the same schedule as
``elevator_scan`` / ``token_shift`` (see :mod:`repro.kernels.common`).

Recurrence (per head, f32 accumulation):

    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    o_t = r_t · (S_{t-1} + u k_t^T v_t)

Entry points: :func:`wkv_pallas` (inference forward),
:func:`wkv_pallas_train` (training forward: also emits ``s_hist``, the
state entering each chunk — the one residual the reverse sweep in
:mod:`repro.kernels.wkv.bwd` cannot recompute in its own direction), and
the ``*_summary`` variants which additionally emit the segment decay
product ``a_seg`` — the diag-decay half of the (A, S) segment summary the
sequence-parallel protocol (:mod:`repro.kernels.wkv.seqpar`) forwards
across the mesh instead of gathering tokens.
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
    validate_divisible,
)


def _wkv_fwd_body(
    r_ref, k_ref, v_ref, w_ref, u_ref, h0_ref, out_ref, s_out_ref, s_ref,
    *, chunk: int, s_hist_ref=None, a_out_ref=None, a_acc_ref=None,
):
    # Boundary: chunk 0 withdraws the constant h0 instead of a token.
    reset_carry(s_ref, h0_ref[0, 0], seq_axis=2)
    if a_acc_ref is not None:
        # Segment-summary mode: the decay product accumulates multiplicatively,
        # so its boundary constant is the monoid identity 1 (not 0).
        reset_carry(a_acc_ref, jnp.ones(a_acc_ref.shape, a_acc_ref.dtype),
                    seq_axis=2)

    if s_hist_ref is not None:
        # Training: record the state *entering* this chunk — the only
        # staged value the reverse sweep (bwd.py) cannot recompute in its
        # own direction (it is a forward-flowing quantity).
        s_hist_ref[0, 0, 0] = s_ref[...]

    r = r_ref[0, 0].astype(jnp.float32)        # (chunk, dh)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)           # (1, dh)

    # Decay-ratio factorization, all in registers/VMEM (nothing staged):
    #   cum_excl[t] = sum_{s<t} log w_s, w_total = prod over the chunk.
    logw = jnp.log(jnp.clip(w, 1e-8, 1.0))
    cum_incl = cumsum_rows(logw, chunk)
    cum_excl = cum_incl - logw
    cum_last = cum_incl[chunk - 1:]            # (1, dh)
    w_total = jnp.exp(cum_last)                # (1, dh)

    if a_acc_ref is not None:
        # Per-segment summary: A_seg = prod over every chunk's w_total — the
        # diag-decay half of the (A, S) pair that crosses the mesh axis in
        # the sequence-parallel protocol (seqpar.py).  Rides its own tiny
        # VMEM carry exactly like S.
        a_acc_ref[...] = a_acc_ref[...] * w_total
        a_out_ref[0, 0] = a_acc_ref[...]       # last grid step wins

    r_dec = r * jnp.exp(cum_excl)              # r_t * D_{<t}
    k_inv = k * jnp.exp(-cum_incl)             # k_s / D_{<=s}
    k_rem = k * jnp.exp(cum_last - cum_incl)  # k_s * D_{(s..L]}

    # Intra-chunk attention: A[t,s] = (r_t D_{<t}) · (k_s / D_{<=s}), s < t,
    # plus the u-bonus on the diagonal.
    scores = jax.lax.dot_general(
        r_dec, k_inv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                           # (chunk, chunk)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(si < ti, scores, 0.0)
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)  # (chunk, 1)
    intra = jnp.dot(scores, v, preferred_element_type=jnp.float32) + bonus * v

    # Inter-chunk read: withdraw the entering state token from VMEM.
    S = s_ref[...]                              # (dh, dh)
    inter = jnp.dot(r_dec, S, preferred_element_type=jnp.float32)
    out_ref[0, 0] = (intra + inter).astype(out_ref.dtype)

    # State update + token hand-off (retag TID -> TID + 1).
    S_new = S * w_total.T + jax.lax.dot_general(
        k_rem, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s_ref[...] = S_new
    s_out_ref[0, 0] = S_new                     # last grid step wins


def wkv_kernel(
    r_ref, k_ref, v_ref, w_ref, u_ref, h0_ref, out_ref, s_out_ref, s_ref,
    *, chunk: int,
):
    _wkv_fwd_body(
        r_ref, k_ref, v_ref, w_ref, u_ref, h0_ref, out_ref, s_out_ref, s_ref,
        chunk=chunk,
    )


def wkv_train_kernel(
    r_ref, k_ref, v_ref, w_ref, u_ref, h0_ref,
    out_ref, s_out_ref, s_hist_ref, s_ref, *, chunk: int,
):
    _wkv_fwd_body(
        r_ref, k_ref, v_ref, w_ref, u_ref, h0_ref, out_ref, s_out_ref, s_ref,
        chunk=chunk, s_hist_ref=s_hist_ref,
    )


def wkv_summary_kernel(
    r_ref, k_ref, v_ref, w_ref, u_ref, h0_ref,
    out_ref, s_out_ref, a_out_ref, s_ref, a_acc_ref, *, chunk: int,
):
    _wkv_fwd_body(
        r_ref, k_ref, v_ref, w_ref, u_ref, h0_ref, out_ref, s_out_ref, s_ref,
        chunk=chunk, a_out_ref=a_out_ref, a_acc_ref=a_acc_ref,
    )


def wkv_train_summary_kernel(
    r_ref, k_ref, v_ref, w_ref, u_ref, h0_ref,
    out_ref, s_out_ref, s_hist_ref, a_out_ref, s_ref, a_acc_ref,
    *, chunk: int,
):
    _wkv_fwd_body(
        r_ref, k_ref, v_ref, w_ref, u_ref, h0_ref, out_ref, s_out_ref, s_ref,
        chunk=chunk, s_hist_ref=s_hist_ref, a_out_ref=a_out_ref,
        a_acc_ref=a_acc_ref,
    )


def _wkv_pallas_call(r, k, v, w, u, h0, *, chunk, interpret, with_hist,
                     with_summary=False):
    b, h, t, dh = r.shape
    validate_divisible("T", t, chunk)
    if u.shape != (h, dh):
        raise ValueError(f"u shape {u.shape} != {(h, dh)}")
    if h0.shape != (b, h, dh, dh):
        raise ValueError(f"h0 shape {h0.shape} != {(b, h, dh, dh)}")
    n_chunks = t // chunk

    grid = (b, h, n_chunks)
    seq_spec = pl.BlockSpec((1, 1, chunk, dh), lambda bi, hi, si: (bi, hi, si, 0))
    state_spec = pl.BlockSpec((1, 1, dh, dh), lambda bi, hi, si: (bi, hi, 0, 0))
    out_specs = (seq_spec, state_spec)
    out_shape = (
        jax.ShapeDtypeStruct((b, h, t, dh), r.dtype),
        jax.ShapeDtypeStruct((b, h, dh, dh), jnp.float32),
    )
    if with_hist:
        out_specs += (pl.BlockSpec(
            (1, 1, 1, dh, dh), lambda bi, hi, si: (bi, hi, si, 0, 0)
        ),)
        out_shape += (
            jax.ShapeDtypeStruct((b, h, n_chunks, dh, dh), jnp.float32),
        )
    if with_summary:
        # (B, H, 1, Dh): the trailing (1, Dh) block equals the array's.
        out_specs += (pl.BlockSpec(
            (1, 1, 1, dh), lambda bi, hi, si: (bi, hi, 0, 0)
        ),)
        out_shape += (jax.ShapeDtypeStruct((b, h, 1, dh), jnp.float32),)
    kernels = {
        (False, False): wkv_kernel,
        (True, False): wkv_train_kernel,
        (False, True): wkv_summary_kernel,
        (True, True): wkv_train_summary_kernel,
    }
    kernel = functools.partial(kernels[(with_hist, with_summary)], chunk=chunk)
    scratch_shapes = [pltpu.VMEM((dh, dh), jnp.float32)]
    if with_summary:
        scratch_shapes.append(pltpu.VMEM((1, dh), jnp.float32))  # A_seg carry
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            seq_spec,  # r
            seq_spec,  # k
            seq_spec,  # v
            seq_spec,  # w
            head_row_spec(dh),  # u
            state_spec,  # h0
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(r, k, v, w, u.reshape(h, 1, dh), h0)
    if with_summary:
        outs = outs[:-1] + (outs[-1][:, :, 0],)
    return outs


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    h0: jax.Array,
    *,
    chunk: int = 64,
    interpret: bool = False,
):
    """Fused WKV sweep.  r/k/v/w: (B, H, T, Dh); u: (H, Dh);
    h0: (B, H, Dh, Dh).  Returns (out (B,H,T,Dh) r.dtype, S (B,H,Dh,Dh) f32).
    """
    return _wkv_pallas_call(
        r, k, v, w, u, h0, chunk=chunk, interpret=interpret, with_hist=False
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas_train(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    h0: jax.Array,
    *,
    chunk: int = 64,
    interpret: bool = False,
):
    """Forward sweep for training: like :func:`wkv_pallas` but additionally
    emits ``s_hist`` (B, H, N, Dh, Dh) — the state entering each chunk.

    ``s_hist`` is the one residual the reverse elevator sweep stages
    through HBM: N small (Dh × Dh) tokens per (batch, head), versus the
    ~6 T·Dh decay tensors + (T/chunk)·chunk² score matrices the autodiff
    path saves.  Everything else is recomputed inside the backward kernel.
    """
    return _wkv_pallas_call(
        r, k, v, w, u, h0, chunk=chunk, interpret=interpret, with_hist=True
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas_summary(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    h0: jax.Array,
    *,
    chunk: int = 64,
    interpret: bool = False,
):
    """Forward sweep emitting the per-segment summary: like
    :func:`wkv_pallas` but additionally returns ``a_seg`` (B, H, Dh), the
    product of every decay in the segment.

    ``(a_seg, S_out)`` is the segment summary of the sequence-parallel
    protocol (:mod:`repro.kernels.wkv.seqpar`): composing it across a mesh
    axis (``core.chunk_scan.DIAG_STATE`` monoid) reconstructs every shard's
    entering state from O(Dh²) bytes per hop — no token re-gather.
    """
    return _wkv_pallas_call(
        r, k, v, w, u, h0, chunk=chunk, interpret=interpret,
        with_hist=False, with_summary=True,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas_train_summary(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    h0: jax.Array,
    *,
    chunk: int = 64,
    interpret: bool = False,
):
    """Training forward with the segment summary: returns
    ``(out, S_out, s_hist, a_seg)`` — the union of
    :func:`wkv_pallas_train` and :func:`wkv_pallas_summary` outputs in one
    sweep (one HBM read of the inputs)."""
    return _wkv_pallas_call(
        r, k, v, w, u, h0, chunk=chunk, interpret=interpret,
        with_hist=True, with_summary=True,
    )
