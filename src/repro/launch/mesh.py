"""Production meshes (TPU v5e pods).

Single pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips — the "pod" axis crosses
the inter-pod DCN/ICI boundary; gradient all-reduce over it is the slow
link that gradient compression targets.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e per-chip hardware constants (roofline terms).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW_PER_LINK = 50e9          # B/s per link (~45-50 GB/s on v5e)
ICI_LINKS = 4                   # 2D torus: 4 links per chip


def _make_mesh(shape, axes):
    """``jax.make_mesh`` in Auto axis mode (GSPMD picks the shardings the
    rules leave open)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small mesh for CPU tests (requires >= data*model host devices)."""
    return _make_mesh((data, model), ("data", "model"))


def make_seq_mesh(n: int | None = None, axis: str = "seq"):
    """1-D mesh over host devices for sequence-parallel runs (benches and
    the multi-device CI lane; production meshes reuse the model axis via
    the ``prefill_seq`` rules instead)."""
    if n is None:
        n = len(jax.devices())
    return _make_mesh((n,), (axis,))
