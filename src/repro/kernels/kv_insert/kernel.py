"""Pallas row insert: one decode token's K and V rows per request, written
into one layer of a stacked KV cache in place.

The kernel takes the caches as ``(L, B, Hkv, Dh, S)``, the logical
transpose of the model's ``(L, B, Hkv, S, Dh)``.  Those are the same
bytes: XLA keeps a cache of long ``S`` and short ``Dh`` with ``S`` as its
minor physical dimension, which is what decode attention reads best, so
the transposes around the call are free.  A grid step takes ``rows``
requests.  It copies the 128-position block that holds each request's
row from HBM into VMEM, sets that one lane, and copies the block back,
every copy of the step in flight at once.  The caches are aliased to the
outputs, so no other byte of them moves; a request whose slot is ``S``
copies nothing and writes nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Positions of one copied block: the lanes of a vector register.
LANES = 128
#: VMEM one grid step may hold: its K and V blocks and its new rows (each
#: ``(Hkv, Dh, 1)`` row padded to a full lane tile, double-buffered).
STEP_BYTES = 8 * 2**20


def row_bytes(h: int, d: int, itemsize: int) -> int:
    """VMEM one request takes in a grid step: its K and V blocks, and its
    two new rows twice over (the pipeline's double buffer)."""
    return 6 * h * d * LANES * itemsize


def rows_per_step(b: int, h: int, d: int, itemsize: int) -> int:
    """Requests a grid step takes: the most that divide ``b`` and fit
    :data:`STEP_BYTES`."""
    cap = max(1, STEP_BYTES // row_bytes(h, d, itemsize))
    return max(r for r in range(1, min(b, cap) + 1) if b % r == 0)


def _row_insert_kernel(slot_ref, layer_ref, k_row_ref, v_row_ref,
                       k_in_ref, v_in_ref, k_ref, v_ref, buf, sem):
    del k_in_ref, v_in_ref          # aliased to k_ref, v_ref
    rows = buf.shape[1]
    s = k_ref.shape[-1]
    base = pl.program_id(0) * rows
    layer = layer_ref[0]
    caches, new_rows = (k_ref, v_ref), (k_row_ref, v_row_ref)

    def copies(j, to_cache):
        start = pl.multiple_of(slot_ref[base + j] // LANES * LANES, LANES)
        out = []
        for c, cache in enumerate(caches):
            block = cache.at[layer, base + j, :, :, pl.ds(start, LANES)]
            src, dst = (buf.at[c, j], block) if to_cache else (block,
                                                                buf.at[c, j])
            out.append(pltpu.make_async_copy(src, dst,
                                             sem.at[int(to_cache), c, j]))
        return out

    def each_written(fn):
        def body(j, carry):
            @pl.when(slot_ref[base + j] < s)
            def _():
                fn(j)
            return carry
        jax.lax.fori_loop(0, rows, body, 0)

    def copy_all(to_cache):
        def start(j):
            for cp in copies(j, to_cache):
                cp.start()

        def wait(j):
            for cp in copies(j, to_cache):
                cp.wait()

        each_written(start)
        each_written(wait)

    lane = jax.lax.broadcasted_iota(jnp.int32, buf.shape[2:], 2)

    def set_lane(j):
        hit = lane == slot_ref[base + j] % LANES
        for c, new in enumerate(new_rows):
            buf[c, j] = jnp.where(hit, new[j], buf[c, j])

    copy_all(to_cache=False)
    each_written(set_lane)
    copy_all(to_cache=True)


def kv_row_insert(k_t, v_t, k_row, v_row, slot, layer, *,
                  interpret: bool = False):
    """Write ``k_row``/``v_row`` (B, Hkv, Dh) into layer ``layer`` of
    ``k_t``/``v_t`` (L, B, Hkv, Dh, S), ``S`` a multiple of
    :data:`LANES`, at position ``slot`` (B,) of each request; a slot of
    ``S`` writes nothing.  Returns the two caches, updated in place."""
    _, b, h, d, s = k_t.shape
    if s % LANES:
        raise ValueError(f"cache of {s} positions: the row insert needs a "
                         f"multiple of {LANES}")
    rows = rows_per_step(b, h, d, k_t.dtype.itemsize)
    row_spec = pl.BlockSpec((rows, h, d, 1), lambda i, *_: (i, 0, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _row_insert_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // rows,),
            in_specs=[row_spec, row_spec, any_spec, any_spec],
            out_specs=[any_spec, any_spec],
            scratch_shapes=[
                pltpu.VMEM((2, rows, h, d, LANES), k_t.dtype),
                pltpu.SemaphoreType.DMA((2, 2, rows)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_t.shape, k_t.dtype),
                   jax.ShapeDtypeStruct(v_t.shape, v_t.dtype)],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        name="kv_row_insert",
    )(slot.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      k_row.astype(k_t.dtype)[..., None], v_row.astype(v_t.dtype)[..., None],
      k_t, v_t)
