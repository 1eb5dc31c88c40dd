"""Shared carry/grid machinery for the chunked sequence kernels.

Every sequence kernel in this package (``elevator_scan``, ``token_shift``,
``wkv``) runs the same schedule, which is the TPU rendering of the paper's
elevator-node chain (§4.1/§4.3):

* grid ``(batch, ..., seq_chunks)`` with the sequence axis iterating
  *fastest*, so a VMEM scratch is private to its leading-grid tile;
* the inter-chunk carry lives in that scratch — the elevator *token buffer*
  for a Δ=1 edge over chunk space — and is reset at chunk 0 to the boundary
  constant ``C`` (``h0`` or zeros);
* at the end of each grid step the carry is retagged TID → TID+1 by
  overwriting the scratch with this chunk's exit state.

Backward passes run the *reverse* sweep: the same grid, but the block
index maps walk the sequence axis back-to-front (:func:`reversed_chunk`),
so grid step 0 processes the **last** chunk and :func:`reset_carry` seeds
the adjoint carry there (the reverse-boundary constant, e.g. ``dS_out``).
The carry then rides the scratch toward chunk 0 — a Δ=-1 elevator edge.
:func:`rev_cumsum_rows` is the suffix-sum twin of :func:`cumsum_rows` for
the in-kernel adjoint of cumulative decays.

The helpers here centralize that contract plus the chunk/d_block validation
and interpret-mode plumbing the per-kernel ``ops.py`` wrappers share.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "on_tpu",
    "interpret_default",
    "reset_carry",
    "head_row_spec",
    "reversed_chunk",
    "shift_rows",
    "cumsum_rows",
    "rev_cumsum_rows",
    "validate_divisible",
    "pick_d_block",
    "largest_divisor_chunk",
    "halving_chunk",
    "KernelResources",
    "KERNEL_RESOURCE_SPECS",
    "register_kernel_resources",
]


# --------------------------------------------------------------------------
# Backend dispatch (ops.py plumbing)
# --------------------------------------------------------------------------

def on_tpu() -> bool:
    """True when the Pallas kernels compile for real TPU hardware."""
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Interpret-mode default: real lowering on TPU, interpreter elsewhere
    (this container) so the kernels stay testable everywhere."""
    return not on_tpu()


# --------------------------------------------------------------------------
# In-kernel carry helpers
# --------------------------------------------------------------------------

def reset_carry(carry_ref, value=None, *, seq_axis: int = 2) -> None:
    """Reset the VMEM carry scratch at grid step 0 (the elevator boundary).

    ``value`` is the boundary constant ``C`` (e.g. ``h0``); ``None`` means
    zeros.  ``seq_axis`` names the grid axis that walks the sequence chunks
    — it must be the fastest-iterating axis so the scratch never leaks
    across (batch, head/d_block) tiles.

    For forward sweeps grid step 0 is chunk 0.  For reverse sweeps (block
    index maps built with :func:`reversed_chunk`) grid step 0 is the *last*
    chunk, so the same call seeds the adjoint carry at the reverse
    boundary — pass the incoming output-cotangent block as ``value``.
    """
    s = pl.program_id(seq_axis)

    @pl.when(s == 0)
    def _init():
        if value is None:
            carry_ref[...] = jnp.zeros_like(carry_ref)
        else:
            carry_ref[...] = value.astype(carry_ref.dtype)


def head_row_spec(dh: int) -> pl.BlockSpec:
    """BlockSpec of a per-head row vector stored ``(H, 1, Dh)``, on a grid
    whose second axis walks the heads.

    The TPU lowering requires the last two block dims to be multiples of
    (8, 128) or equal to the array's; ``(1, Dh)`` equals them for any Dh,
    where the natural ``(1, Dh)`` block over an ``(H, Dh)`` array does not.
    """
    return pl.BlockSpec((1, 1, dh), lambda bi, hi, *_: (hi, 0, 0))


def reversed_chunk(n_chunks: int):
    """Block-index component for a back-to-front sweep over the seq axis.

    ``reversed_chunk(n)(s) == n - 1 - s``: grid step ``s`` processes chunk
    ``n-1-s``, so the grid still iterates ascending (Pallas requirement)
    while the *blocks* walk last-to-first.  Combined with
    :func:`reset_carry` this puts the carry reset at the last chunk —
    the reverse elevator boundary.
    """
    return lambda s: n_chunks - 1 - s


def shift_rows(v: jax.Array, delta: int, fill: float) -> jax.Array:
    """Shift rows by ``delta`` (toward higher indices when positive, lower
    when negative), filling vacated rows with ``fill``.

    The in-VMEM rendering of an elevator shift: rows are sublanes, so this
    lowers to sublane rotates plus a select against the boundary constant.
    Negative ``delta`` is the reverse-sweep direction (adjoint flows).
    """
    rows = v.shape[0]
    rolled = jnp.roll(v, delta, axis=0)
    idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    if delta >= 0:
        keep = idx >= delta
    else:
        keep = idx < rows + delta
    return jnp.where(keep, rolled, jnp.asarray(fill, v.dtype))


def cumsum_rows(v: jax.Array, rows: int) -> jax.Array:
    """Inclusive cumulative sum along axis 0 via log-depth doubling.

    Hillis–Steele on the VPU — ``ceil(log2(rows))`` shift+add steps, the
    same forwarding network :func:`shift_rows` models, with 0 as the
    identity boundary constant.  Used instead of ``jnp.cumsum`` inside
    kernels so the lowering stays a static chain of vector ops.
    """
    acc = v
    shift = 1
    while shift < rows:
        acc = acc + shift_rows(acc, shift, 0.0)
        shift *= 2
    return acc


def rev_cumsum_rows(v: jax.Array, rows: int) -> jax.Array:
    """Inclusive *suffix* sum along axis 0: out[s] = sum_{t >= s} v[t].

    The reverse-sweep twin of :func:`cumsum_rows` — the same Hillis–Steele
    doubling with negative shifts.  This is the in-kernel adjoint of a
    cumulative sum: if ``y = cumsum(x)`` then ``dx = rev_cumsum(dy)``,
    which is exactly what the backward kernels need for the cumulative
    log-decay chains.
    """
    acc = v
    shift = 1
    while shift < rows:
        acc = acc + shift_rows(acc, -shift, 0.0)
        shift *= 2
    return acc


# --------------------------------------------------------------------------
# Static resource declarations (repro.analysis.resources)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelResources:
    """Static VMEM footprint of one Pallas kernel configuration.

    Declared by each kernel's ``ops.py`` next to the ``pallas_call`` it
    mirrors (``register_kernel_resources``) and audited — pure shape
    math, nothing traced or executed — by
    :mod:`repro.analysis.resources` against the per-core VMEM budget.

    ``blocks``/``scratch`` are ``(name, block_shape, itemsize)`` tuples:
    exactly the BlockSpec block shapes (ins + outs) and scratch_shapes of
    the ``pallas_call``, so a kernel edit that grows a tile without
    updating its declaration shows up as a divergence in review.
    """

    kernel: str                 # e.g. "wkv.fwd"
    location: str               # repo-path-like site of the pallas_call
    grid: tuple[int, ...]
    blocks: tuple[tuple[str, tuple[int, ...], int], ...]
    scratch: tuple[tuple[str, tuple[int, ...], int], ...] = ()

    def block_bytes(self) -> int:
        return sum(math.prod(s) * isz for _, s, isz in self.blocks)

    def scratch_bytes(self) -> int:
        return sum(math.prod(s) * isz for _, s, isz in self.scratch)

    def vmem_bytes(self, *, double_buffer: int = 2) -> int:
        """Estimated VMEM high-water mark: every in/out block held
        ``double_buffer``-deep (the pipelined prefetch) + scratch."""
        return double_buffer * self.block_bytes() + self.scratch_bytes()

    def grid_steps(self) -> int:
        return math.prod(self.grid) if self.grid else 1


#: name -> spec fn.  A spec fn has signature ``fn(cfg) -> KernelResources
#: | None`` (None: kernel not applicable to this config) and must *raise*
#: (ValueError) on invalid geometry — the audit converts that into an
#: error finding, which is how the wrappers' divisibility validation
#: (``validate_divisible`` / ``pick_d_block`` / chunk resolution) gets
#: checked without building a single array.
KERNEL_RESOURCE_SPECS: dict[str, Callable] = {}


def register_kernel_resources(name: str):
    """Decorator: register a resource-spec fn under ``name``."""

    def deco(fn):
        KERNEL_RESOURCE_SPECS[name] = fn
        return fn

    return deco


# --------------------------------------------------------------------------
# Chunk / block validation (kernel wrappers)
# --------------------------------------------------------------------------

def validate_divisible(name: str, total: int, block: int) -> None:
    if block < 1 or total % block:
        raise ValueError(f"{name}={total} not divisible by block={block}")


def pick_d_block(d: int, cap: int = 512) -> int:
    """Feature-axis block: lane-friendly cap, must tile D exactly."""
    d_block = min(d, cap)
    if d % d_block:
        raise ValueError(f"D={d} not divisible by d_block={d_block}")
    return d_block


def largest_divisor_chunk(t: int, chunk: int) -> int:
    """Largest c <= min(chunk, t) with t % c == 0 (always exists: c=1)."""
    for c in range(min(chunk, t), 0, -1):
        if t % c == 0:
            return c
    return 1


def halving_chunk(t: int, chunk: int) -> int:
    """Shrink ``chunk`` by halving until it divides ``t`` (power-of-two
    kernels: preserves two-ness when the caller starts from a power of two)."""
    c = min(chunk, t)
    while c > 1 and t % c:
        c //= 2
    return c
