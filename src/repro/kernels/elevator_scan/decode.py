"""Pallas TPU decode micro-kernel: persistent RG-LRU state across tokens.

The RG-LRU analogue of :mod:`repro.kernels.wkv.decode` (ROADMAP item (d)).
Stateful decode used to force the unfused jnp path
(``elevator_scan(..., use_kernel=False if t == 1 else None)`` in
``model/recurrent.py``), so the (B, d_rnn) hidden state round-tripped HBM
on every generated token even on TPU.  Here the window of K decode steps
is swept in ONE kernel invocation on a ``(batch, d_blocks)`` grid: the
whole window is one block and a loop inside the kernel carries ``h`` from
token to token — the same Δ=1 elevator carry the chunked kernel uses over
chunk space, now over *decode steps*: one HBM read of ``h0`` and one
write of the exit state per K tokens instead of per token.  K is
arbitrary up to :data:`ELEVATOR_DECODE_WINDOW_MAX` (no chunk structure,
no divisibility constraint); K == 1 is the classic single-token step.

Differentiable through :func:`elevator_decode_diff` (recompute-over-stage:
the backward is the closed-form adjoint of the linear recurrence — a
reverse linear scan — with the forward states recomputed, so the only
residuals are the primal inputs).  Dispatch:
``ops.elevator_scan(decode=True)`` sends windows up to
:data:`ELEVATOR_DECODE_WINDOW_MAX` tokens here; longer stateful sweeps
(cache prefill) fall through to the chunked paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import pick_d_block
from repro.kernels.elevator_scan.ref import elevator_scan_ref_f32

# Stateful (decode) dispatches at or below this many tokens take the
# window kernel; above it the chunked elevator kernel wins (log-depth
# intra-chunk doubling amortizes).  Matches the WKV decode threshold.
ELEVATOR_DECODE_WINDOW_MAX = 64

__all__ = [
    "ELEVATOR_DECODE_WINDOW_MAX",
    "elevator_decode_window_pallas",
    "elevator_decode_diff",
]


def elevator_decode_window_kernel(a_ref, x_ref, h0_ref, out_ref):
    """K-step window, grid (batch, d_blocks): the whole window is one block.

    An in-kernel loop walks the K tokens; step ``i`` withdraws the state
    deposited by step ``i-1`` (step 0 withdraws the boundary constant
    ``h0``) — the elevator hand-off of the chunked kernel with decode
    steps as the chunk axis.
    """
    h = h0_ref[0].astype(jnp.float32)                   # (1, d_block)
    # Static unroll (K <= ELEVATOR_DECODE_WINDOW_MAX): the TPU lowering
    # refuses a dynamic single-row index into a block, a static one it takes.
    for i in range(a_ref.shape[1]):
        row = slice(i, i + 1)
        h = (a_ref[0, row, :].astype(jnp.float32) * h
             + x_ref[0, row, :].astype(jnp.float32))    # hand-off: TID -> TID+1
        out_ref[0, row, :] = h.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def elevator_decode_window_pallas(
    a: jax.Array,
    x: jax.Array,
    h0: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """K-token decode window of h[t] = a[t]*h[t-1] + x[t].

    a/x: (B, K, D), 1 <= K <= ELEVATOR_DECODE_WINDOW_MAX; h0: (B, D).
    Returns h (B, K, D) in ``x.dtype`` — bit-identical to K single steps
    chained, with one HBM round-trip of the state instead of K.
    """
    b, t, d = x.shape
    if h0.shape != (b, d):
        raise ValueError(f"h0 shape {h0.shape} != {(b, d)}")
    if t > ELEVATOR_DECODE_WINDOW_MAX:
        raise ValueError(f"decode window K={t} > {ELEVATOR_DECODE_WINDOW_MAX}"
                         f": the kernel unrolls K")
    d_block = pick_d_block(d)
    seq_spec = pl.BlockSpec((1, t, d_block), lambda bi, di: (bi, 0, di))
    return pl.pallas_call(
        elevator_decode_window_kernel,
        grid=(b, d // d_block),
        in_specs=[
            seq_spec, seq_spec,
            # h0 as (B, 1, D): see elevator_scan_pallas.
            pl.BlockSpec((1, 1, d_block), lambda bi, di: (bi, 0, di)),
        ],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, d), x.dtype),
        interpret=interpret,
    )(a, x, h0[:, None])


# --------------------------------------------------------------------------
# Differentiable wrapper (ops.elevator_scan decode dispatch)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def elevator_decode_diff(interpret, use_pallas, a, x, h0):
    """Differentiable decode-window elevator scan.  Returns h (B, K, D)
    in ``x.dtype``.

    Forward: the window kernel (``use_pallas=True``) or the sequential
    jnp scan — for short decode windows the sequential form IS the
    cheapest jnp rendering.  Backward: the closed-form adjoint of the
    linear recurrence (g[t] = dh[t] + a[t+1]*g[t+1], swept in reverse),
    recompute-over-stage — only the primals are saved.
    """
    if use_pallas:
        return elevator_decode_window_pallas(a, x, h0, interpret=interpret)
    return elevator_scan_ref_f32(a, x, h0).astype(x.dtype)


def _elevator_decode_fwd(interpret, use_pallas, a, x, h0):
    return elevator_decode_diff(interpret, use_pallas, a, x, h0), (a, x, h0)


def _elevator_decode_bwd(interpret, use_pallas, res, dh):
    a, x, h0 = res
    a32 = a.astype(jnp.float32)
    dh32 = dh.astype(jnp.float32)
    h = elevator_scan_ref_f32(a, x, h0)                  # recompute
    h_prev = jnp.concatenate(
        [h0.astype(jnp.float32)[:, None], h[:, :-1]], axis=1
    )
    # g[t] = dh[t] + a[t+1] g[t+1]: the same recurrence run on reversed
    # time with the decay shifted one step left (identity at the end).
    a_next = jnp.concatenate([a32[:, 1:], jnp.ones_like(a32[:, :1])], axis=1)
    g = jnp.flip(
        elevator_scan_ref_f32(jnp.flip(a_next, 1), jnp.flip(dh32, 1),
                              jnp.zeros_like(h0, dtype=jnp.float32)), 1
    )
    da = g * h_prev
    dx = g
    dh0 = a32[:, 0] * g[:, 0]
    return da.astype(a.dtype), dx.astype(x.dtype), dh0.astype(h0.dtype)


elevator_decode_diff.defvjp(_elevator_decode_fwd, _elevator_decode_bwd)
