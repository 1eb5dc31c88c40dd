"""Pallas TPU decode micro-kernels: persistent WKV state across tokens.

Decode is the repo's worst memory offender: every generated token used to
read and write the full (B, H, Dh, Dh) WKV state through HBM, because the
``t == 1`` dispatch punted to the jnp sequential oracle and the serve loop
re-dispatched per token.  These kernels are the paper's loop-carried-value
argument applied to serving:

* :func:`wkv_decode_window_pallas` — a ``(B, H, K, Dh)`` window of K
  decode steps swept in ONE kernel invocation on a ``(batch, head)`` grid.
  The whole window is one block and a loop inside the kernel walks its
  tokens, carrying S from token to token — the same Δ=1 elevator carry
  the chunked kernel uses over chunk space, now over *decode steps*.  Per
  token: ``o = r · (S + u kᵀv)``, ``S' = diag(w) S + kᵀv``, f32
  accumulation, bf16 I/O like the fused path.  One HBM read + one write
  of S per K tokens instead of per token; the K-1 intermediate states
  never leave the core (``cost_model.wkv_decode_traffic`` counts exactly
  these bytes).  K is arbitrary up to :data:`DECODE_WINDOW_MAX` (no
  divisibility constraint — there is no chunk structure).
* :func:`wkv_decode_pallas` — the same kernel at K = 1.

Unlike the chunked kernel there is no decay-ratio factorization: the
sequential form is exact and the per-step work is O(Dh²), so nothing is
gained by exponent bookkeeping — and losing it removes the clip-range
coupling between window length and decay magnitude.

Both entry points are differentiable through :func:`wkv_decode_diff`
(recompute-over-stage: the backward is the sequential manual sweep
``wkv_chunked_bwd_ref(chunk=1)``; the only residuals are the primals).
Dispatch (``ops.wkv_fused(decode=True)``) sends windows up to
:data:`DECODE_WINDOW_MAX` tokens here and longer stateful sweeps (e.g.
long-prompt prefill-into-cache) to the chunked kernel, where the MXU score
matrices start paying for themselves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import head_row_spec
from repro.kernels.wkv.ref import wkv_chunked_bwd_ref, wkv_sequential_ref

# Stateful (decode) dispatches at or below this many tokens take the
# window kernel; above it the chunked elevator kernel wins (intra-chunk
# score matmuls amortize on the MXU).  64 = one chunk of the fused path.
DECODE_WINDOW_MAX = 64

__all__ = [
    "DECODE_WINDOW_MAX",
    "wkv_decode_pallas",
    "wkv_decode_window_pallas",
    "wkv_decode_diff",
]


def _decode_token(r, k, v, w, u, S):
    """One WKV step on (1, dh) token rows against the (dh, dh) state.

    Returns ``(o, S_new)`` in f32.  ``o = r·(S + u kᵀv)`` splits into the
    state matvec plus a u-weighted rank-1 bonus: ``o = r @ S + (r·u·k) v``.
    """
    kv = jax.lax.dot_general(
        k, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )                                                   # kᵀv: (dh, dh)
    inter = jnp.dot(r, S, preferred_element_type=jnp.float32)   # (1, dh)
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)           # (1, 1)
    o = inter + bonus * v
    S_new = S * w.T + kv
    return o, S_new


def wkv_decode_window_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, h0_ref,
                             out_ref, s_out_ref):
    """K-step window, grid (batch, head): the whole window is one block.

    An in-kernel loop walks the K tokens; step ``i`` withdraws the state
    deposited by step ``i-1`` (step 0 withdraws the boundary constant
    ``h0``) — the elevator hand-off of the chunked kernel with decode
    steps as the chunk axis.  HBM sees one read (``h0``) and one write
    (``s_out``) of S per K tokens.
    """
    u = u_ref[0].astype(jnp.float32)                    # (1, dh)
    S = h0_ref[0, 0]
    # Static unroll (K <= DECODE_WINDOW_MAX): the TPU lowering refuses a
    # dynamic single-row index into a bf16 block, a static one it takes.
    for i in range(r_ref.shape[2]):
        row = slice(i, i + 1)
        o, S = _decode_token(
            r_ref[0, 0, row, :].astype(jnp.float32),
            k_ref[0, 0, row, :].astype(jnp.float32),
            v_ref[0, 0, row, :].astype(jnp.float32),
            w_ref[0, 0, row, :].astype(jnp.float32),
            u, S,
        )                                               # hand-off: TID -> TID+1
        out_ref[0, 0, row, :] = o.astype(out_ref.dtype)
    s_out_ref[0, 0] = S


def _validate(r, u, h0):
    b, h, t, dh = r.shape
    if u.shape != (h, dh):
        raise ValueError(f"u shape {u.shape} != {(h, dh)}")
    if h0.shape != (b, h, dh, dh):
        raise ValueError(f"h0 shape {h0.shape} != {(b, h, dh, dh)}")


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv_decode_pallas(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    h0: jax.Array,
    *,
    interpret: bool = False,
):
    """Single decode step.  r/k/v/w: (B, H, 1, Dh); u: (H, Dh);
    h0: (B, H, Dh, Dh).  Returns (out (B,H,1,Dh) r.dtype, S (B,H,Dh,Dh) f32).
    The window kernel at K = 1: state read once, written once.
    """
    if r.shape[2] != 1:
        raise ValueError(f"wkv_decode_pallas is single-step; got T={r.shape[2]}")
    return _decode_window_call(r, k, v, w, u, h0, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv_decode_window_pallas(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    h0: jax.Array,
    *,
    interpret: bool = False,
):
    """K-token decode window.  r/k/v/w: (B, H, K, Dh),
    1 <= K <= DECODE_WINDOW_MAX (no divisibility constraint); u: (H, Dh);
    h0: (B, H, Dh, Dh).  Returns (out (B,H,K,Dh) r.dtype, S (B,H,Dh,Dh)
    f32) — bit-identical to K single steps chained, with one HBM
    round-trip of S instead of K.
    """
    return _decode_window_call(r, k, v, w, u, h0, interpret=interpret)


def _decode_window_call(r, k, v, w, u, h0, *, interpret):
    b, h, t, dh = r.shape
    _validate(r, u, h0)
    if t > DECODE_WINDOW_MAX:
        raise ValueError(f"decode window K={t} > {DECODE_WINDOW_MAX}: the "
                         f"kernel unrolls K, longer sweeps take the chunked "
                         f"kernel")
    seq_spec = pl.BlockSpec((1, 1, t, dh), lambda bi, hi: (bi, hi, 0, 0))
    state_spec = pl.BlockSpec((1, 1, dh, dh), lambda bi, hi: (bi, hi, 0, 0))
    return pl.pallas_call(
        wkv_decode_window_kernel,
        grid=(b, h),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            head_row_spec(dh),  # u
            state_spec,
        ],
        out_specs=(seq_spec, state_spec),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, t, dh), r.dtype),
            jax.ShapeDtypeStruct((b, h, dh, dh), jnp.float32),
        ),
        interpret=interpret,
    )(r, k, v, w, u.reshape(h, 1, dh), h0)


# --------------------------------------------------------------------------
# Differentiable wrapper (ops.wkv_fused decode dispatch)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def wkv_decode_diff(interpret, use_pallas, r, k, v, w, u, h0):
    """Differentiable decode-window WKV.  Returns ``(out, S_out)`` with
    ``out`` in ``r.dtype`` and ``S_out`` float32.

    Forward: the single-step kernel (K == 1) or the window kernel
    (``use_pallas=True``), else the jnp sequential oracle — for decode
    windows the sequential form IS the cheapest jnp rendering (no chunk
    structure to exploit).  Backward: the manual sequential sweep
    (``wkv_chunked_bwd_ref`` at chunk 1) — recompute-over-stage, so the
    only residuals are the primal inputs.
    """
    if use_pallas:
        if r.shape[2] == 1:
            return wkv_decode_pallas(r, k, v, w, u, h0, interpret=interpret)
        return wkv_decode_window_pallas(r, k, v, w, u, h0, interpret=interpret)
    out, s_out = wkv_sequential_ref(r, k, v, w, u, h0)
    return out.astype(r.dtype), s_out


def _wkv_decode_fwd(interpret, use_pallas, r, k, v, w, u, h0):
    out = wkv_decode_diff(interpret, use_pallas, r, k, v, w, u, h0)
    return out, (r, k, v, w, u, h0)


def _wkv_decode_bwd(interpret, use_pallas, res, cts):
    r, k, v, w, u, h0 = res
    d_out, d_s_out = cts
    dr, dk, dv, dw, du, dh0 = wkv_chunked_bwd_ref(
        r, k, v, w, u, h0, d_out, d_s_out, chunk=1
    )
    return (
        dr.astype(r.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        dw.astype(w.dtype),
        du.astype(u.dtype),
        dh0.astype(h0.dtype),
    )


wkv_decode_diff.defvjp(_wkv_decode_fwd, _wkv_decode_bwd)
