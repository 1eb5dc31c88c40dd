"""Fused WKV elevator kernel (RWKV6 matrix-state recurrence).

Paper mapping (§4.3, token buffers): the WKV state ``S`` — a (Dh × Dh)
matrix per (batch, head) — is the loop-carried value of a Δ=1 elevator
edge over sequence-chunk space.  The Pallas kernel keeps it in a
``pltpu.VMEM((dh, dh))`` scratch: each grid step along the chunk axis
withdraws the predecessor's token (the entering state), fuses the
intra-chunk decay-ratio attention with the inter-chunk state read and the
state update, and deposits the exit state for its successor.  ``h0`` is
the ``fromThreadOrConst`` boundary constant withdrawn by chunk 0.  The
jnp fallback (``ref.wkv_chunked_ref``) computes identical math but stages
every per-chunk intermediate and the scan carry through HBM — the
Fig. 1b scratchpad pattern the kernel eliminates.

Training closes the same loop in reverse: the backward pass's
loop-carried value is the adjoint state ``dS`` (same (Dh × Dh) shape),
and ``bwd.py`` carries it in a VMEM scratch over a back-to-front chunk
sweep — reset at the *last* chunk to the incoming state cotangent,
per-chunk decays recomputed in-fabric instead of staged through HBM.
``vjp.py`` ties the two sweeps into a ``jax.custom_vjp`` so ``wkv_fused``
is differentiable end-to-end on both the kernel and jnp paths.

The same edge exists between chips: ``seqpar.py`` composes per-device
``(decay-product, exit-state)`` segment summaries across a ``seq`` mesh
axis (the ``DIAG_STATE`` monoid of :mod:`repro.core.chunk_scan`), so a
sequence-sharded model forwards O(Dh²) summaries point-to-point instead
of all-gathering tokens or states — device-space elevator edges, forward
and (by ppermute transposition) reverse for training.

Ships as kernel.py (forward pallas_call, plus the training variant that
records chunk-entry states and the summary variants that emit the segment
decay product), bwd.py (reverse sweep), vjp.py (custom_vjp assembly),
ops.py (dispatch + chunk policy), seqpar.py (sequence-parallel protocol)
and ref.py (sequential + chunked oracles, forward and backward, plus the
jnp segment-summary helpers).
"""

from repro.kernels.wkv.ops import wkv_fused, wkv_fused_summary
from repro.kernels.wkv.ref import (
    wkv_chunked_bwd_ref,
    wkv_chunked_ref,
    wkv_sequential_ref,
)
from repro.kernels.wkv.seqpar import wkv_headshard, wkv_seq_local, wkv_seqshard

__all__ = [
    "wkv_fused",
    "wkv_fused_summary",
    "wkv_headshard",
    "wkv_seq_local",
    "wkv_seqshard",
    "wkv_chunked_ref",
    "wkv_chunked_bwd_ref",
    "wkv_sequential_ref",
]
