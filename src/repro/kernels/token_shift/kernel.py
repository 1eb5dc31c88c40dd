"""Pallas TPU kernel: fused token-shift (depthwise causal short conv).

The paper's convolution example (Fig. 1c): each thread loads its element
*once* and receives the neighboring elements through elevator shifts instead
of re-loading them.  On TPU:

* each sequence chunk is loaded into VMEM exactly once (HBM traffic = N
  elements, vs. K*N for the naive per-tap gather — the paper's Fig. 1a);
* the K-1 trailing rows of the previous chunk persist in a VMEM scratch — a
  (K-1)-entry *token buffer* forwarding values across the chunk boundary;
* the shifted operands are produced by sublane rotates inside VMEM (fabric
  forwarding), multiplied by per-channel taps and accumulated on the VPU.

Grid: (batch, d_blocks, seq_chunks), sequence fastest so the scratch carry
is private per (batch, d_block) and reset at chunk 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pick_d_block, reset_carry, validate_divisible

MAX_TAPS = 8  # hardware-aligned token-buffer budget (paper uses 16)


def token_shift_kernel(x_ref, w_ref, out_ref, carry_ref, *, taps: int, chunk: int):
    reset_carry(carry_ref, seq_axis=2)

    x = x_ref[0].astype(jnp.float32)          # (chunk, d_block)
    w = w_ref[...].astype(jnp.float32)        # (taps, d_block)
    carry = carry_ref[...]                    # (taps-1, d_block) prev tail

    # Extended block: previous chunk's tail followed by this chunk.  The
    # elevator shift for tap k is then a static slice of `ext`.
    ext = jnp.concatenate([carry, x], axis=0)  # (chunk + taps - 1, d_block)

    acc = w[0] * x
    for k in range(1, taps):
        # Rows [taps-1-k : taps-1-k+chunk] of ext == x shifted down by k.
        shifted = ext[taps - 1 - k:taps - 1 - k + chunk]
        acc = acc + w[k] * shifted

    out_ref[0, :, :] = acc.astype(out_ref.dtype)
    # Forward this chunk's tail into the token buffer for the next chunk.
    carry_ref[...] = x[chunk - (taps - 1):, :]


@functools.partial(jax.jit, static_argnames=("interpret", "chunk"))
def token_shift_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Depthwise causal conv: out[t] = Σ_k w[k]·x[t-k].  x: (B,T,D), w: (K,D)."""
    b, t, d = x.shape
    taps = w.shape[0]
    if taps < 2 or taps > MAX_TAPS:
        raise ValueError(f"taps must be in [2, {MAX_TAPS}], got {taps}")
    if w.shape[1] != d:
        raise ValueError(f"w dim {w.shape[1]} != D {d}")
    chunk = min(chunk, t)
    validate_divisible("T", t, chunk)
    if chunk < taps:
        raise ValueError(f"chunk {chunk} must be >= taps {taps}")
    d_block = pick_d_block(d)

    grid = (b, d // d_block, t // chunk)
    kernel = functools.partial(token_shift_kernel, taps=taps, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((taps, d_block), lambda bi, di, si: (0, di)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block), lambda bi, di, si: (bi, si, di)),
        out_shape=jax.ShapeDtypeStruct((b, t, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((taps - 1, d_block), jnp.float32)],
        interpret=interpret,
    )(x, w)
