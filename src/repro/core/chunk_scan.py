"""Windowed linear-recurrence scans built from elevator carries.

The paper's prefix-sum example (Fig. 6) is the degenerate case of

    h[t] = a[t] * h[t-1] + b[t]          (a ≡ 1, b = loaded value)

with the inter-thread edge ``fromThreadOrConst<sum, Δ=1, C=0>``.  This module
generalizes the pattern into the workhorse behind the SSM/hybrid
architectures (RG-LRU, RWKV6 decay):

* :func:`linear_scan` — reference associative scan (log-depth, in-core).
* :func:`chunked_linear_scan` — two-level scheme: dense within-chunk scans +
  an across-chunk carry chain.  The carry chain is exactly a cascade of
  elevator nodes with Δ=1 over chunk space; the Pallas kernel
  (:mod:`repro.kernels.elevator_scan`) keeps the carry in VMEM scratch.
* :func:`device_linear_scan_carry` — the same composition across a *mesh*
  axis: each shard contributes its segment summary ``(A, B)``; a log-depth
  Hillis–Steele chain of ``ppermute`` shifts (device-space elevator nodes)
  delivers the entering carry to every shard.  Point-to-point, no gather.

All three run ONE composition law, the :class:`SegmentMonoid`:

    (a1, b1) ∘then∘ (a2, b2) = (a2·a1, a2★b1 + b2)

where ``★`` is the monoid's action of a decay on a state.  Two instances
cover every recurrence in this repo:

* :data:`ELEMENTWISE` — decay and state share a shape; ``★`` is ``*``.
  RG-LRU / diagonal scans (and the paper's prefix sum with a ≡ 1).
* :data:`DIAG_STATE` — decay is a (..., Dh) vector acting on the *rows* of
  a (..., Dh, Dh) matrix state: ``a ★ S = a[..., :, None] * S``.  This is
  the WKV segment summary (diag-decay ⊗ S) of :mod:`repro.kernels.wkv`:
  a whole device's sequence shard collapses to the O(Dh²) pair
  ``(prod w, S_exit)``, which is all that ever crosses the mesh axis.

The *adjoint* of either recurrence is the same monoid swept the other way
(the backward of ``S' = a★S + B`` carries ``dS = a★dS' + dB``), so
``reverse=True`` on the device sweeps gives the device-space reverse
elevator used for sequence-sharded training.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import device_comm

__all__ = [
    "SegmentMonoid",
    "ELEMENTWISE",
    "DIAG_STATE",
    "linear_scan",
    "chunked_linear_scan",
    "device_linear_scan_carry",
]


@dataclasses.dataclass(frozen=True)
class SegmentMonoid:
    """Associative composition of ``(decay, state)`` segment summaries.

    ``scale(a, b)`` is the action of a decay on a state-shaped value.
    Decays always compose elementwise (``a2 * a1``); only the action on the
    state varies between recurrences.  The identity element is ``(1, 0)``
    — exactly the elevator boundary constants :func:`device_comm.device_shift`
    injects at the edge of the fabric.
    """

    scale: Callable[[jax.Array, jax.Array], jax.Array]

    def compose(self, first, second):
        """Summary of ``first``-then-``second`` (first applied first)."""
        a1, b1 = first
        a2, b2 = second
        return a2 * a1, self.scale(a2, b1) + b2

    def apply(self, segment, h):
        """Run a summarized segment from state ``h``: ``a★h + b``."""
        a, b = segment
        return self.scale(a, h) + b


ELEMENTWISE = SegmentMonoid(scale=lambda a, b: a * b)
DIAG_STATE = SegmentMonoid(scale=lambda a, b: a[..., :, None] * b)


def _compose(first, second):
    """Back-compat alias: the elementwise composition law."""
    return ELEMENTWISE.compose(first, second)


def linear_scan(a: jax.Array, b: jax.Array, *, axis: int = 0, h0=None) -> jax.Array:
    """h[t] = a[t]*h[t-1] + b[t] with h[-1] = h0 (default 0). Log-depth."""
    if h0 is not None:
        # Fold h0 into the first step: h[0] = a[0]*h0 + b[0].  Promote to
        # jax arrays first so the fold is unconditional — the old
        # ``hasattr(b, "at")`` guard silently dropped h0 for numpy inputs.
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        h0 = jnp.asarray(h0, b.dtype)
        idx = [slice(None)] * b.ndim
        idx[axis] = slice(0, 1)
        first = tuple(idx)
        b = b.at[first].set(a[first] * h0 + b[first])
    _, h = jax.lax.associative_scan(ELEMENTWISE.compose, (a, b), axis=axis)
    return h


def chunked_linear_scan(
    a: jax.Array,
    b: jax.Array,
    *,
    chunk: int,
    axis: int = 0,
    h0=None,
    monoid: SegmentMonoid = ELEMENTWISE,
) -> jax.Array:
    """Two-level scan: intra-chunk associative scans + inter-chunk carries.

    Mirrors the dMT-CGRA structure: the within-chunk scan is the dataflow
    graph body; the across-chunk carry is the elevator edge (Δ=1 over chunk
    index, C = h0).  Functionally identical to :func:`linear_scan` — the
    tests assert allclose — but exposes the chunked schedule the Pallas
    kernel implements with a VMEM carry.

    With ``monoid=DIAG_STATE`` the state ``b`` carries extra trailing
    dimensions (e.g. a (Dh, Dh) matrix per step decayed by a (Dh,) vector
    ``a``) — the same composition :func:`device_linear_scan_carry` runs
    across a mesh axis for sequence-sharded WKV.
    """
    a = jnp.moveaxis(a, axis, 0)
    b = jnp.moveaxis(b, axis, 0)
    t = a.shape[0]
    if t % chunk:
        raise ValueError(f"sequence length {t} not divisible by chunk {chunk}")
    n_chunks = t // chunk
    rest_a = a.shape[1:]
    rest_b = b.shape[1:]
    ac = a.reshape((n_chunks, chunk) + rest_a)
    bc = b.reshape((n_chunks, chunk) + rest_b)

    # Intra-chunk inclusive scans (dense, parallel over chunks).
    acum, bcum = jax.lax.associative_scan(monoid.compose, (ac, bc), axis=1)

    # Chunk summaries = last element of each inclusive scan.
    a_sum = acum[:, -1]
    b_sum = bcum[:, -1]

    # Across-chunk carry chain: exclusive scan over chunk summaries.  This is
    # the elevator cascade: carry[k] enters chunk k.
    def step(carry, summary):
        new_carry = monoid.apply(summary, carry)
        return new_carry, carry

    h_init = jnp.zeros(rest_b, b.dtype) if h0 is None else jnp.broadcast_to(
        jnp.asarray(h0, b.dtype), rest_b
    )
    _, carries = jax.lax.scan(step, h_init, (a_sum, b_sum))

    # Inject the entering carry into every position of the chunk.
    h = monoid.apply((acum, bcum), carries[:, None])
    h = h.reshape((t,) + rest_b)
    return jnp.moveaxis(h, 0, axis)


def device_linear_scan_carry(
    a_seg: jax.Array,
    b_seg: jax.Array,
    axis_name: str,
    *,
    monoid: SegmentMonoid = ELEMENTWISE,
    reverse: bool = False,
):
    """Entering carry per shard for a sequence sharded over ``axis_name``.

    ``a_seg``/``b_seg`` are the local segment summaries (product of decays,
    accumulated input).  Returns ``(carry_a, carry_b)`` such that the state
    entering shard ``i`` is ``monoid.scale(carry_a, h0) + carry_b`` — i.e.
    the composition of all predecessor segments.  log2(n) ppermute hops
    (Hillis–Steele), each a device-space elevator shift with the identity
    segment (1, 0) as the boundary constant.

    ``reverse=True`` runs the sweep from the *last* shard toward shard 0:
    the carry entering shard ``i`` is then the composition of all successor
    segments (applied last-to-first).  This is the device-space reverse
    elevator — the adjoint carry ``dS`` of a forward recurrence flows
    exactly this way during sequence-sharded training.
    """
    n = jax.lax.axis_size(axis_name)
    sgn = -1 if reverse else 1
    acc_a, acc_b = a_seg, b_seg
    d = 1
    while d < n:
        shifted_a = device_comm.device_shift(acc_a, axis_name, delta=sgn * d, fill=1.0)
        shifted_b = device_comm.device_shift(acc_b, axis_name, delta=sgn * d, fill=0.0)
        # Predecessor block applied first, current block second.
        acc_a, acc_b = monoid.compose((shifted_a, shifted_b), (acc_a, acc_b))
        d *= 2
    # acc now holds the inclusive composition; the entering carry is the
    # predecessor's inclusive value — one more elevator shift.
    carry_a = device_comm.device_shift(acc_a, axis_name, delta=sgn, fill=1.0)
    carry_b = device_comm.device_shift(acc_b, axis_name, delta=sgn, fill=0.0)
    return carry_a, carry_b
