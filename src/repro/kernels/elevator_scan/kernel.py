"""Pallas TPU kernel: chunked decayed scan with a VMEM elevator carry.

TPU-native adaptation of the paper's elevator-node chain (§4.1/§4.3):

* the sequence is tiled into chunks; each chunk is one grid step;
* the inter-chunk state hand-off lives in a VMEM scratch register — the
  *token buffer* of a Δ=1 elevator node over chunk space (never HBM);
* within a chunk, the recurrence is solved with log2(chunk) Hillis–Steele
  doubling steps on the VPU (8×128 vector registers), i.e. the in-fabric
  forwarding network;
* the initial state ``h0`` plays the elevator constant C at the boundary.

Grid layout: ``(batch, d_blocks, seq_chunks)`` — the sequence axis iterates
fastest, so the carry scratch is private to a (batch, d_block) tile and is
reset when ``seq_chunk == 0``.

Tiling: chunk is a multiple of 8 (sublanes), d_block a multiple of 128
(lanes), so shifts along the chunk axis are sublane rotates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    pick_d_block,
    reset_carry,
    shift_rows,
    validate_divisible,
)


def _chunk_scan_doubling(a: jax.Array, x: jax.Array, chunk: int):
    """Inclusive scan of h=a*h_prev+x along axis 0 via log-depth doubling.

    Returns (a_cum, h_local): cumulative decay products and local scan
    results (carry-free).  Both are float32.
    """
    h = x
    acc = a
    shift = 1
    while shift < chunk:
        # Compose with the segment ending `shift` rows above (elevator shift
        # with identity constant: a=1, b=0 injected at the boundary).
        a_shift = shift_rows(acc, shift, fill=1.0)
        h_shift = shift_rows(h, shift, fill=0.0)
        h = acc * h_shift + h
        acc = acc * a_shift
        shift *= 2
    return acc, h


def elevator_scan_kernel(
    a_ref, x_ref, h0_ref, out_ref, carry_ref, *, chunk: int, n_chunks: int
):
    # Boundary: chunk 0 receives the elevator constant h0.
    reset_carry(carry_ref, h0_ref[0], seq_axis=2)

    a = a_ref[0].astype(jnp.float32)   # (chunk, d_block)
    x = x_ref[0].astype(jnp.float32)

    a_cum, h_local = _chunk_scan_doubling(a, x, chunk)

    carry_in = carry_ref[...]           # (1, d_block) token buffer
    h = h_local + a_cum * carry_in      # inject entering carry
    out_ref[0, :, :] = h.astype(out_ref.dtype)

    # Hand the token to the next chunk (retag TID -> TID + 1).
    carry_ref[...] = h[-1:, :]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def elevator_scan_pallas(
    a: jax.Array,
    x: jax.Array,
    h0: jax.Array | None = None,
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """h[t] = a[t]*h[t-1] + x[t] scanned along axis 1 of (B, T, D) arrays."""
    b, t, d = x.shape
    validate_divisible("T", t, chunk)
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    d_block = pick_d_block(d)
    n_chunks = t // chunk
    if h0 is None:
        h0 = jnp.zeros((b, d), x.dtype)
    if h0.shape != (b, d):
        raise ValueError(f"h0 shape {h0.shape} != {(b, d)}")

    grid = (b, d // d_block, n_chunks)
    kernel = functools.partial(
        elevator_scan_kernel, chunk=chunk, n_chunks=n_chunks
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, chunk, d_block), lambda bi, di, si: (bi, si, di)),
            # h0 as (B, 1, D): the trailing (1, d_block) block's row dim
            # equals the array's, which the TPU tiling rule requires.
            pl.BlockSpec((1, 1, d_block), lambda bi, di, si: (bi, 0, di)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block), lambda bi, di, si: (bi, si, di)),
        out_shape=jax.ShapeDtypeStruct((b, t, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, d_block), jnp.float32)],
        interpret=interpret,
    )(a, x, h0[:, None])
