"""Public op: fused WKV recurrence with automatic backend dispatch.

``use_kernel=None`` auto-selects (the ``elevator_scan`` convention): the
Pallas kernel on TPU, the jnp chunked reference elsewhere.  ``use_kernel``
is the escape hatch — ``False`` forces the jnp path (models on CPU),
``True`` forces the kernel (interpret mode off-TPU, for parity tests).
Both paths are differentiable through the same ``custom_vjp``
(:mod:`repro.kernels.wkv.vjp`): the kernel path pairs the forward elevator
sweep with the reverse VMEM-adjoint sweep (``bwd.py``), so auto mode is
safe under ``jax.grad`` — the kernel is the TPU default for training too,
not just inference.

Chunk policy: ``chunk`` is a *request*.  When it does not divide T the
dispatch picks the largest valid divisor and warns — never the old silent
``chunk = t`` rewrite, which could blow the decay-ratio exponent range for
long odd sequences (``wkv_chunked_ref`` itself now raises instead).  The
warning fires once per distinct ``(T, chunk)`` pair: dispatch runs at
trace time under the model's outer jit, and a per-retrace warning is pure
log spam.

Decode dispatch: ``decode=True`` marks a *stateful serving* call (the
model threads it from ``decode_step``).  Windows up to
:data:`~repro.kernels.wkv.decode.DECODE_WINDOW_MAX` tokens take the
persistent-state decode kernels (:mod:`repro.kernels.wkv.decode`): S is
read from HBM once and written once per window, intermediate states ride
a VMEM carry, and there is no chunk-divisibility constraint (a decode
window has no chunk structure).  Longer stateful sweeps — e.g. filling
the cache from a long prompt — fall through to the chunked elevator
kernel, where the intra-chunk score matmuls amortize on the MXU.
``decode=None`` (the default) infers ``t == 1``, so plain single-token
calls hit the decode path with no caller change.
"""

from __future__ import annotations

import contextlib
import warnings

import jax
import jax.numpy as jnp

from repro.kernels.common import (
    interpret_default,
    largest_divisor_chunk,
    on_tpu,
    register_kernel_resources,
    KernelResources,
)
from repro.kernels.wkv.decode import DECODE_WINDOW_MAX, wkv_decode_diff
from repro.kernels.wkv.ref import wkv_sequential_ref
from repro.kernels.wkv.vjp import wkv_diff, wkv_diff_summary

# (T, chunk) pairs already warned about, keyed by warn scope — dedupes
# across retraces/calls *within* a scope, so two models (or two test
# cases) hitting the same awkward (T, chunk) each get their own warning.
# The old module-global flat set deduped across unrelated configs: the
# second model's chunk adjustment was silent for the whole process life.
_CHUNK_WARNED: dict[str | None, set[tuple[int, int]]] = {}

# Active scope stack (chunk_warning_scope); empty -> the None scope.
_WARN_SCOPE: list[str | None] = []


def reset_chunk_warnings(scope: str | None = None, *, all_scopes: bool = False):
    """Forget warned (T, chunk) pairs — one scope, or every scope."""
    if all_scopes:
        _CHUNK_WARNED.clear()
    else:
        _CHUNK_WARNED.pop(scope, None)


@contextlib.contextmanager
def chunk_warning_scope(tag: str | None):
    """Scope chunk-adjustment warnings to ``tag`` for the duration —
    model code wraps its dispatch so each config warns independently."""
    _WARN_SCOPE.append(tag)
    try:
        yield
    finally:
        _WARN_SCOPE.pop()


def resolve_chunk(t: int, chunk: int, *, scope: str | None = None,
                  tpu_kernel: bool = False) -> int:
    """Largest divisor of ``t`` no larger than ``chunk``; warns on adjust
    (once per distinct ``(t, chunk)`` per warn scope).

    ``tpu_kernel``: the chunk feeds the compiled TPU kernel, whose
    ``(1, 1, chunk, Dh)`` blocks need ``chunk`` a multiple of 8 or all of
    ``t``; a divisor that is neither raises instead of warning.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    c = largest_divisor_chunk(t, chunk)
    if tpu_kernel and c % 8 and c != t:
        raise ValueError(
            f"wkv chunk={chunk} on T={t}: the largest divisor {c} is neither "
            f"a multiple of 8 nor T, which the TPU kernel's tiling refuses"
        )
    if c != min(chunk, t):
        key = scope if scope is not None else (
            _WARN_SCOPE[-1] if _WARN_SCOPE else None
        )
        seen = _CHUNK_WARNED.setdefault(key, set())
        if (t, chunk) not in seen:
            seen.add((t, chunk))
            warnings.warn(
                f"wkv chunk={chunk} does not divide T={t}; using chunk={c}",
                stacklevel=3,
            )
    return c


# NOTE: intentionally un-jitted — called under the model's outer jit; a
# nested jit would cache across the scan_unroll() lowering flag.
def wkv_fused(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    h0: jax.Array | None = None,
    *,
    chunk: int = 64,
    use_kernel: bool | None = None,
    decode: bool | None = None,
    warn_scope: str | None = None,
):
    """RWKV6 WKV:  S_t = diag(w_t) S_{t-1} + k_t^T v_t;
    o_t = r_t · (S_{t-1} + u k_t^T v_t).

    r/k/v/w: (B, H, T, Dh); u: (H, Dh); h0: (B, H, Dh, Dh) or None (zeros).
    Returns ``(out, S_out)`` with ``out`` (B,H,T,Dh) in ``r.dtype`` and
    ``S_out`` (B,H,Dh,Dh) in float32.  Differentiable on every path.

    ``decode`` marks a stateful serving call (see module docstring):
    windows of at most ``DECODE_WINDOW_MAX`` tokens take the
    persistent-state decode kernels; ``None`` infers ``t == 1``.

    bf16 I/O: r/k/v/w may arrive in bf16 (or any float dtype) — no
    caller-side upcast needed.  Every backend accumulates in float32
    internally and ``out`` comes back in the input dtype, so feeding bf16
    halves the unavoidable HBM traffic without touching the recurrence
    math (see ``cost_model.wkv_traffic``'s ``io`` term).
    """
    b, h, t, dh = r.shape
    if h0 is None:
        h0 = jnp.zeros((b, h, dh, dh), jnp.float32)

    kernel = on_tpu() if use_kernel is None else use_kernel
    if decode is None:
        decode = t == 1
    if decode and t <= DECODE_WINDOW_MAX:
        if kernel:
            # Persistent-state decode kernel: one HBM round-trip of S per
            # window, VMEM carry between the window's tokens.
            return wkv_decode_diff(interpret_default(), True, r, k, v, w, u, h0)
        # jnp fallback: the sequential oracle is the cheapest form for a
        # short stateful window (no chunk structure to exploit), and
        # autodiff through a few steps is trivial.
        out, s_out = wkv_sequential_ref(r, k, v, w, u, h0)
        return out.astype(r.dtype), s_out
    c = resolve_chunk(t, chunk, scope=warn_scope,
                      tpu_kernel=kernel and not interpret_default())
    return wkv_diff(c, interpret_default(), bool(kernel), r, k, v, w, u, h0)


def wkv_fused_summary(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    h0: jax.Array | None = None,
    *,
    chunk: int = 64,
    use_kernel: bool | None = None,
    warn_scope: str | None = None,
):
    """Like :func:`wkv_fused` but additionally returns ``a_seg`` (B, H, Dh)
    float32 — the segment decay product, i.e. the diag half of the
    ``(A, S)`` segment summary.

    This is the local building block of the sequence-parallel protocol
    (:mod:`repro.kernels.wkv.seqpar`): each device calls it on its shard
    with a zero entering state, then only ``(a_seg, S_out)`` — O(Dh²) per
    (batch, head) — crosses the mesh axis.  Dispatch/chunk policy and
    differentiability match :func:`wkv_fused` (the ``a_seg`` cotangent
    folds into ``dw`` in closed form, see ``vjp.wkv_diff_summary``).
    """
    b, h, t, dh = r.shape
    if h0 is None:
        h0 = jnp.zeros((b, h, dh, dh), jnp.float32)
    kernel = on_tpu() if use_kernel is None else use_kernel
    c = resolve_chunk(t, chunk, scope=warn_scope,
                      tpu_kernel=kernel and not interpret_default())
    return wkv_diff_summary(
        c, interpret_default(), bool(kernel), r, k, v, w, u, h0
    )


# --------------------------------------------------------------------------
# Static resource declarations (repro.analysis.resources)
# --------------------------------------------------------------------------

_WKV_DH = 64  # RWKV6 head-dim convention (model.recurrent.RWKV_HEAD_DIM)


def _wkv_geometry(cfg, t: int, chunk: int):
    import jax.numpy as jnp

    if cfg.d_model % _WKV_DH:
        raise ValueError(
            f"{cfg.name}: d_model={cfg.d_model} not divisible by the WKV "
            f"head dim {_WKV_DH}"
        )
    h = cfg.d_model // _WKV_DH
    c = resolve_chunk(t, chunk)
    isz = jnp.dtype(cfg.dtype).itemsize
    return h, c, isz


@register_kernel_resources("wkv.fwd")
def _wkv_fwd_resources(cfg, *, t: int = 4096, chunk: int = 64):
    """Chunked forward elevator sweep (inference: no state history)."""
    if "rwkv" not in tuple(cfg.pattern):
        return None
    dh = _WKV_DH
    h, c, isz = _wkv_geometry(cfg, t, chunk)
    seq = (1, 1, c, dh)
    state = (1, 1, dh, dh)
    return KernelResources(
        kernel="wkv.fwd",
        location="src/repro/kernels/wkv/kernel.py:_wkv_pallas_call",
        grid=(1, h, t // c),
        blocks=(
            ("r", seq, isz), ("k", seq, isz), ("v", seq, isz),
            ("w", seq, isz), ("u", (1, 1, dh), isz), ("h0", state, 4),
            ("out", seq, isz), ("s_out", state, 4),
        ),
        scratch=(("S", (dh, dh), 4),),
    )


@register_kernel_resources("wkv.train")
def _wkv_train_resources(cfg, *, t: int = 4096, chunk: int = 64):
    """Forward sweep with the per-chunk state history the VJP replays."""
    base = _wkv_fwd_resources(cfg, t=t, chunk=chunk)
    if base is None:
        return None
    dh = _WKV_DH
    return KernelResources(
        kernel="wkv.train",
        location="src/repro/kernels/wkv/kernel.py:_wkv_pallas_call",
        grid=base.grid,
        blocks=base.blocks + (("s_hist", (1, 1, 1, dh, dh), 4),),
        scratch=base.scratch,
    )


@register_kernel_resources("wkv.decode_window")
def _wkv_decode_resources(cfg, *, window: int = DECODE_WINDOW_MAX):
    """Persistent-state decode window: the whole window is one block, S
    rides an in-kernel loop across it."""
    if "rwkv" not in tuple(cfg.pattern):
        return None
    import jax.numpy as jnp

    if cfg.d_model % _WKV_DH:
        raise ValueError(
            f"{cfg.name}: d_model={cfg.d_model} not divisible by the WKV "
            f"head dim {_WKV_DH}"
        )
    dh = _WKV_DH
    h = cfg.d_model // dh
    isz = jnp.dtype(cfg.dtype).itemsize
    seq = (1, 1, window, dh)
    state = (1, 1, dh, dh)
    return KernelResources(
        kernel="wkv.decode_window",
        location="src/repro/kernels/wkv/decode.py:wkv_decode_window_pallas",
        grid=(1, h),
        blocks=(
            ("r", seq, isz), ("k", seq, isz), ("v", seq, isz),
            ("w", seq, isz), ("u", (1, 1, dh), isz), ("h0", state, 4),
            ("out", seq, isz), ("s_out", state, 4),
        ),
    )
