"""Device-space elevator nodes — the paper's fabric edges mapped onto ICI.

A CGRA elevator node moves a token from thread ``TID`` to ``TID + delta`` and
injects a constant at the boundary.  Across a TPU mesh the same pattern is a
``lax.ppermute`` (collective-permute) along a named axis: point-to-point,
producer→consumer, no global barrier — in contrast to the all-gather /
shared-buffer pattern that mirrors GPGPU scratchpad staging.

All functions here must run inside ``shard_map`` (they use named axes).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

__all__ = [
    "device_shift",
    "halo_exchange",
    "ring_pass",
    "seq_carry_scan",
]


def device_shift(x: jax.Array, axis_name: str, delta: int = 1, fill=0.0) -> jax.Array:
    """Elevator shift across devices: shard ``i`` receives shard ``i - delta``.

    Boundary shards (no producer) receive ``fill`` — the elevator constant C.
    Exactly one collective-permute; O(|x|) bytes point-to-point on ICI.
    """
    n = jax.lax.axis_size(axis_name)
    if delta == 0:
        return x
    perm = [(i, i + delta) for i in range(n) if 0 <= i + delta < n]
    shifted = jax.lax.ppermute(x, axis_name, perm)
    idx = jax.lax.axis_index(axis_name)
    src = idx - delta
    has_producer = (src >= 0) & (src < n)
    return jnp.where(has_producer, shifted, jnp.asarray(fill, x.dtype))


def ring_pass(x: jax.Array, axis_name: str, delta: int = 1) -> jax.Array:
    """Cyclic variant (ring): shard ``i`` receives shard ``(i - delta) mod n``.

    Used by ring-style forwarding (e.g. rotating K/V or operand tiles so a
    value loaded from HBM once visits every shard — the eLDST pattern).
    """
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + delta) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def halo_exchange(
    x: jax.Array,
    axis_name: str,
    *,
    left: int = 0,
    right: int = 0,
    seq_axis: int = 0,
    fill=0.0,
) -> jax.Array:
    """Forward boundary tokens between neighboring sequence shards.

    ``x`` is the local chunk of a sequence-sharded tensor.  The result is the
    local chunk extended with ``left`` trailing tokens of the previous shard
    and ``right`` leading tokens of the next shard — delivered point-to-point
    (one ppermute per side), never by all-gathering the sequence.  Edge
    shards receive ``fill`` (elevator constant) in the missing halo.

    This implements local/sliding-window attention's K/V neighborhood and the
    token-shift halo of RWKV-style models across shards.
    """
    parts = []
    if left:
        tail = jax.lax.slice_in_dim(x, x.shape[seq_axis] - left, x.shape[seq_axis], axis=seq_axis)
        parts.append(device_shift(tail, axis_name, delta=1, fill=fill))
    parts.append(x)
    if right:
        head = jax.lax.slice_in_dim(x, 0, right, axis=seq_axis)
        parts.append(device_shift(head, axis_name, delta=-1, fill=fill))
    if len(parts) == 1:
        return x
    return jnp.concatenate(parts, axis=seq_axis)


def seq_carry_scan(
    chunk_fn,
    carry_init: Any,
    x: jax.Array,
    axis_name: str,
    *,
    reverse: bool = False,
):
    """Sequential carry chain across sequence shards (elevator Δ=1 chain).

    ``chunk_fn(carry, x_local) -> (carry_out, y_local)`` runs on every shard;
    the carry produced by shard ``i`` is forwarded to shard ``i+1`` via
    ppermute.  Shard 0 uses ``carry_init`` (the elevator constant).  The chain
    serializes across shards by construction — it is the *exact* dataflow of
    the paper's prefix-sum example (Fig. 6) at ICI granularity.  Use
    :mod:`repro.core.chunk_scan` for the log-depth alternative when the
    recurrence is associative.

    ``reverse=True`` runs the chain from the *last* shard toward shard 0
    (a Δ=-1 edge): shard ``n-1`` uses ``carry_init`` and each carry is
    forwarded to shard ``i-1``.  This is the device-space reverse elevator
    — the sweep direction of adjoint carries (e.g. the WKV ``dS``) during
    sequence-sharded training.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    # Position along the sweep: hop k activates the k-th shard in sweep
    # order (ascending indices forward, descending in reverse).
    pos = (n - 1 - idx) if reverse else idx
    delta = -1 if reverse else 1

    init = jax.tree.map(jnp.asarray, carry_init)
    # Each shard must observe the carries of all predecessors.  We unroll the
    # shard chain: at hop k every shard runs its chunk against the carry it
    # currently holds, but only the shard whose turn it is (pos == k) keeps
    # its freshly produced output; carries propagate one hop per iteration.
    # Cost: n hops (pipeline-friendly; XLA overlaps the permutes).
    carry_out, y = chunk_fn(init, x)
    for k in range(1, n):
        shifted = jax.tree.map(
            lambda t: device_shift(t, axis_name, delta=delta, fill=0.0), carry_out
        )
        carry_in = jax.tree.map(
            lambda new, ini: jnp.where(pos >= k, new, ini.astype(new.dtype)),
            shifted, init,
        )
        carry_new, y_new = chunk_fn(carry_in, x)
        keep = pos == k
        y = jax.tree.map(lambda a, b: jnp.where(keep, b, a), y, y_new)
        carry_out = jax.tree.map(lambda a, b: jnp.where(pos >= k, b, a), carry_out, carry_new)
    return carry_out, y
