"""Public op: tiled matmul with operand-forwarding reuse accounting."""

from __future__ import annotations

import functools

import jax

from repro.core.cost_model import Traffic
from repro.kernels.common import interpret_default, on_tpu
from repro.kernels.matmul_fwd.kernel import matmul_fwd_pallas
from repro.kernels.matmul_fwd.ref import matmul_ref


# NOTE: intentionally un-jitted — called under the model's outer jit; a
# nested jit would cache across the scan_unroll() lowering flag.
def matmul_fwd(
    a, b, *, block_m=256, block_n=256, block_k=256, use_kernel: bool | None = None
):
    kernel = on_tpu() if use_kernel is None else use_kernel
    if kernel:
        return matmul_fwd_pallas(
            a, b, block_m=block_m, block_n=block_n, block_k=block_k,
            interpret=interpret_default(),
        )
    return matmul_ref(a, b)


def tile_traffic(m, n, k, block_m, block_n, block_k, itemsize=2) -> Traffic:
    """HBM bytes for the tiled schedule (per §3.3's reuse law).

    Naive per-element: 2·M·N·K element loads.  Tiled: each output tile
    re-streams A and B panels once per K-block.
    """
    tiles = (m // block_m) * (n // block_n)
    per_tile = (k // block_k) * (block_m * block_k + block_k * block_n)
    return Traffic(
        dram_bytes=(tiles * per_tile + m * n) * itemsize,
        fabric_bytes=(2 * m * n * k - tiles * per_tile) * itemsize,
    )
