"""Public op: flash attention (full / causal / sliding-window, GQA)."""

from __future__ import annotations

import functools

import jax

from repro.kernels.common import (
    KernelResources,
    interpret_default,
    on_tpu,
    register_kernel_resources,
)
from repro.kernels.local_attention.kernel import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    flash_attention_pallas,
)
from repro.kernels.local_attention.ref import attention_blockwise, attention_ref


# NOTE: intentionally un-jitted — called under the model's outer jit; a
# nested jit would cache across the scan_unroll() lowering flag.
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    use_kernel: bool | None = None,
) -> jax.Array:
    """Attention over (B, H, T, D) queries and (B, Hkv, S, D) keys/values.

    Dispatch: Pallas kernel on TPU; on CPU, the blockwise (flash-structured,
    O(T·block) memory) jnp path for long sequences — so dry-run lowering
    reflects the kernel's memory/flop profile — and the exact masked-einsum
    reference for short ones.  All three agree numerically (tests).
    """
    kernel = on_tpu() if use_kernel is None else use_kernel
    if kernel:
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale,
            interpret=interpret_default(),
        )
    if q.shape[2] > 1024 or k.shape[2] > 1024:
        from repro.core.lowering import scan_unroll

        # Under unrolled-cost lowering, bigger blocks keep the HLO compact.
        block = 2048 if scan_unroll() is True else 512
        return attention_blockwise(
            q, k, v, causal=causal, window=window, scale=scale, block=block
        )
    return attention_ref(q, k, v, causal=causal, window=window, scale=scale)


# --------------------------------------------------------------------------
# Static resource declarations (repro.analysis.resources)
# --------------------------------------------------------------------------

_ATTN_KINDS = ("attn", "local", "global")


@register_kernel_resources("local_attention.flash")
def _flash_attention_resources(cfg, *, t: int = 4096):
    """Flash attention tile footprint (sliding-window for local layers)."""
    import jax.numpy as jnp

    kinds = set(cfg.pattern) & set(_ATTN_KINDS)
    if not kinds:
        return None
    if cfg.num_heads % max(cfg.num_kv_heads, 1):
        raise ValueError(
            f"{cfg.name}: Hq={cfg.num_heads} not a multiple of "
            f"Hkv={cfg.num_kv_heads}"
        )
    d = cfg.head_dim
    bq, bk = DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    window = cfg.attn_window if "local" in kinds else None
    t_pad = -(-t // bq) * bq
    s_pad = -(-t // bk) * bk
    n_q_blocks = t_pad // bq
    n_kv_blocks = s_pad // bk
    if window is not None:
        n_kv_steps = min(n_kv_blocks, (window + bq) // bk + 2)
    else:
        n_kv_steps = n_kv_blocks
    isz = jnp.dtype(cfg.dtype).itemsize
    return KernelResources(
        kernel="local_attention.flash",
        location=("src/repro/kernels/local_attention/kernel.py:"
                  "flash_attention_pallas"),
        grid=(cfg.num_heads, n_q_blocks, n_kv_steps),
        blocks=(
            ("q", (1, bq, d), isz), ("k", (1, bk, d), isz),
            ("v", (1, bk, d), isz), ("out", (1, bq, d), isz),
        ),
        scratch=(
            ("m", (bq, 128), 4), ("l", (bq, 128), 4), ("acc", (bq, d), 4),
        ),
    )
