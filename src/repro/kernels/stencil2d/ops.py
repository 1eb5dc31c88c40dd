"""Public op: 5-point stencil sweep (hotspot/SRAD building block)."""

from __future__ import annotations

import functools

import jax

from repro.kernels.common import interpret_default, on_tpu
from repro.kernels.stencil2d.kernel import stencil2d_pallas
from repro.kernels.stencil2d.ref import stencil2d_ref


# NOTE: intentionally un-jitted — called under the model's outer jit; a
# nested jit would cache across the scan_unroll() lowering flag.
def stencil2d(x, coeffs, *, boundary: float = 0.0, use_kernel: bool | None = None):
    kernel = on_tpu() if use_kernel is None else use_kernel
    if kernel:
        h = x.shape[0]
        block_h = 128
        while h % block_h:
            block_h //= 2
        return stencil2d_pallas(
            x, coeffs, block_h=block_h, boundary=boundary,
            interpret=interpret_default(),
        )
    return stencil2d_ref(x, coeffs, boundary)
