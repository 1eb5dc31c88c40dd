"""Sequence-parallel WKV: device-space elevator edges over a mesh axis.

PR 1/2 replaced the group-to-group "stage through HBM + barrier" pattern
*within* a chip: the (Dh × Dh) WKV state rides a VMEM carry between
sequence chunks (forward), and the adjoint ``dS`` rides it back (reverse).
The same elevator edge exists *between* chips.  A sequence-sharded model
that all-gathers the state — or worse, the tokens — to stitch shards
together is the paper's Fig. 1b scratchpad pattern at ICI granularity.

This module removes it with the segment-summary protocol:

1. every device runs the existing fused kernel on its local shard with a
   **zero** entering state, additionally emitting the segment summary
   ``(a_seg, S_exit⁰)`` — the decay product (B, H, Dh) and the exit state
   (B, H, Dh, Dh) (``wkv_fused_summary``);
2. the summaries compose across the ``seq`` mesh axis under the
   ``DIAG_STATE`` monoid (``core.chunk_scan.device_linear_scan_carry``):
   log₂(n) point-to-point ppermute hops, each carrying O(Dh²) bytes —
   device-space elevator nodes, never a token re-gather;
3. each shard reconstructs its true entering state
   ``S_in = carry_a ★ h0 + carry_b`` and adds the (linear) entry
   correction ``(r_t ⊙ D_{<t}) @ S_in`` to its local outputs
   (``ref.wkv_entry_correction``); the final state is read off the last
   shard with one masked psum (again O(Dh²)).

**Training falls out by transposition**: the VJP of a ppermute is the
opposite-direction ppermute, so ``jax.grad`` through this path runs the
composition sweep *backward* — the adjoint ``dS``/``d_a`` summaries hop
last-shard→first exactly as ``device_linear_scan_carry(reverse=True)``
would, while each shard's local gradient goes through the reverse
elevator kernel (``bwd.py``) via the ``wkv_diff_summary`` custom VJP.
Only segment summaries ever cross the axis, forward or backward
(asserted on the jaxpr in ``tests/test_multidevice.py``).

Windows that are not sequence-sharded still need a mesh placement when
the kernel runs on more than one device: :func:`wkv_headshard` splits
batch and heads, which share nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.chunk_scan import DIAG_STATE, device_linear_scan_carry
from repro.kernels.wkv.ops import wkv_fused, wkv_fused_summary
from repro.kernels.wkv.ref import wkv_entry_correction

__all__ = ["wkv_headshard", "wkv_seq_local", "wkv_seqshard"]


def wkv_seq_local(
    r, k, v, w, u, h0, *, axis_name: str, chunk: int = 64,
    use_kernel: bool | None = None,
):
    """Per-shard body of the sequence-parallel WKV (call inside shard_map).

    ``r/k/v/w`` are the *local* sequence shard (B, H, T/n, Dh); ``h0`` is
    the global entering state (replicated over ``axis_name``).  Returns
    ``(out_local, S_out)`` with ``S_out`` the global exit state, identical
    on every shard.
    """
    f32 = jnp.float32
    out0, s0, a_seg = wkv_fused_summary(
        r, k, v, w, u, None, chunk=chunk, use_kernel=use_kernel
    )
    # Compose (A, S) summaries along the mesh axis: the entering state of
    # shard i is carry_a ★ h0 + carry_b (DIAG_STATE monoid, h0 enters
    # shard 0 as the elevator boundary constant).
    carry_a, carry_b = device_linear_scan_carry(
        a_seg, s0, axis_name, monoid=DIAG_STATE
    )
    s_in = DIAG_STATE.scale(carry_a, h0.astype(f32)) + carry_b
    out = (out0.astype(f32) + wkv_entry_correction(r, w, s_in)).astype(r.dtype)
    # Exit state of this shard; the global S_out is the last shard's.  The
    # masked psum moves one more O(Dh²) summary, never activations.
    s_exit = DIAG_STATE.scale(a_seg, s_in) + s0
    idx = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    s_out = jax.lax.psum(jnp.where(idx == n - 1, s_exit, 0.0), axis_name)
    return out, s_out


def wkv_seqshard(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    h0: jax.Array | None = None,
    *,
    mesh,
    seq_axis,
    batch_axis=None,
    chunk: int = 64,
    use_kernel: bool | None = None,
):
    """Sequence-sharded WKV over ``mesh``'s ``seq_axis``.

    Same signature/returns as :func:`repro.kernels.wkv.ops.wkv_fused`
    (``out`` in ``r.dtype``, ``S_out`` float32) plus the mesh placement:
    the T axis of r/k/v/w is sharded over ``seq_axis`` (T must divide
    evenly), the batch axis optionally over ``batch_axis``; u and h0 are
    replicated along ``seq_axis``.  Differentiable — the gradient runs the
    device-space *reverse* elevator (summary ppermutes transposed to the
    opposite direction) composed with the local reverse kernel sweep.
    """
    b, h, t, dh = r.shape
    if h0 is None:
        h0 = jnp.zeros((b, h, dh, dh), jnp.float32)
    seq_spec = P(batch_axis, None, seq_axis, None)
    state_spec = P(batch_axis, None, None, None)
    local = functools.partial(
        wkv_seq_local, axis_name=seq_axis, chunk=chunk, use_kernel=use_kernel
    )
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, seq_spec, P(None, None),
                  state_spec),
        out_specs=(seq_spec, state_spec),
        check_vma=False,
    )
    return fn(r, k, v, w, u, h0)


def wkv_headshard(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    h0: jax.Array,
    *,
    mesh,
    batch_axis=None,
    head_axis=None,
    **kw,
):
    """:func:`~repro.kernels.wkv.ops.wkv_fused` (same arguments and
    returns) with batch over ``batch_axis`` and heads over ``head_axis``.

    Every (batch, head) pair is an independent recurrence, so each device
    runs the fused kernel on its own block and nothing crosses devices.
    The compiler cannot partition a Mosaic kernel by itself: under a
    multi-device mesh the kernel path must run inside a ``shard_map``.
    """
    blk = P(batch_axis, head_axis, None, None)
    fn = jax.shard_map(
        functools.partial(wkv_fused, **kw),
        mesh=mesh,
        in_specs=(blk, blk, blk, blk, P(head_axis, None), blk),
        out_specs=(blk, blk),
        check_vma=False,
    )
    return fn(r, k, v, w, u, h0)
