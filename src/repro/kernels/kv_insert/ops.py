"""Public op: write each request's new K/V row into one layer of a stacked
dense KV cache, in place."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.common import (
    KernelResources,
    interpret_default,
    on_tpu,
    register_kernel_resources,
)
from repro.kernels.kv_insert.kernel import LANES, kv_row_insert, rows_per_step


def write_rows(k_all, v_all, k_row, v_row, slot, layer, *,
               use_kernel: bool | None = None):
    """Write ``k_row``/``v_row`` (B, Hkv, Dh) into layer ``layer`` of
    ``k_all``/``v_all`` (L, B, Hkv, S, Dh) at position ``slot`` (B,) of
    each request; a slot of ``S`` writes nothing.  Returns the two caches.

    Dispatch: the Pallas row kernel on a TPU (``S`` a multiple of
    :data:`LANES`), an XLA scatter elsewhere; ``use_kernel=True`` forces
    the kernel (interpret mode off the TPU, for parity tests).
    """
    kernel = on_tpu() if use_kernel is None else use_kernel
    if kernel:
        k_t, v_t = kv_row_insert(
            jnp.swapaxes(k_all, -1, -2), jnp.swapaxes(v_all, -1, -2),
            k_row, v_row, slot, layer, interpret=interpret_default())
        return jnp.swapaxes(k_t, -1, -2), jnp.swapaxes(v_t, -1, -2)
    rows = jnp.arange(k_all.shape[1])

    def put(cache, new):
        return cache.at[layer, rows, :, slot].set(new.astype(cache.dtype),
                                                  mode="drop")

    return put(k_all, k_row), put(v_all, v_row)


# --------------------------------------------------------------------------
# Static resource declarations (repro.analysis.resources)
# --------------------------------------------------------------------------

@register_kernel_resources("kv_insert.row")
def _row_insert_resources(cfg, *, batch: int = 32):
    """One decode token's K and V rows for ``batch`` requests, a grid
    step's blocks at a time (``scratch`` holds the copied blocks)."""
    if not set(cfg.pattern) & {"attn", "local", "global"}:
        return None
    h, d = cfg.num_kv_heads, cfg.head_dim
    isz = jnp.dtype(cfg.dtype).itemsize
    rows = rows_per_step(batch, h, d, isz)
    return KernelResources(
        kernel="kv_insert.row",
        location="src/repro/kernels/kv_insert/kernel.py:kv_row_insert",
        grid=(batch // rows,),
        blocks=(("k_row", (rows, h, d, 1), isz),
                ("v_row", (rows, h, d, 1), isz)),
        scratch=(("blocks", (2, rows, h, d, LANES), isz),),
    )

