"""Recurrent blocks: RG-LRU (RecurrentGemma/Griffin), RWKV6 (Finch) and
Mamba-2 (SSD, as Granite-4.0-H and other hybrids use it).

Both are direct consumers of the paper's technique: the hidden-state
hand-off h[t-1] -> h[t] is literally ``fromThreadOrConst<h, Δ=1, C=h0>``
(the paper's prefix-sum dataflow, Fig. 6), and the token-shift mixing of
RWKV is ``fromThreadOrConst<x, Δ=1, C=0>``.  Sequence-chunked execution
keeps the carries in VMEM (elevator token buffers) via the
``elevator_scan`` / ``token_shift`` / ``wkv`` Pallas kernels — the last
carrying the matrix-valued WKV state (Dh × Dh per head) across chunks.

Decode is O(1) per token: the recurrent state *is* the entire context —
which is why these archs run the long_500k shape.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.common import on_tpu
from repro.kernels.elevator_scan.ops import elevator_scan
from repro.kernels.ssd.ops import ssd
from repro.kernels.token_shift.ops import token_shift
from repro.kernels.wkv.ops import wkv_fused
from repro.kernels.wkv.ref import wkv_chunked_ref, wkv_sequential_ref
from repro.kernels.wkv.seqpar import wkv_headshard, wkv_seqshard
from repro.model.layers import init_rmsnorm, rms_norm
from repro.model.sharding import (
    active_context,
    axes_size,
    constrain,
    gather_for_use,
    seq_shard_info,
)

_RGLRU_C = 8.0  # Griffin's fixed recurrence-sharpness constant


class RecState(NamedTuple):
    """Decode-time state for one recurrent layer."""

    h: jax.Array           # RG-LRU hidden (B, d_rnn) | RWKV S (B, H, dk, dv)
    #                        | Mamba-2 S (B, H, head_dim, d_state)
    conv: jax.Array        # conv tail (B, width-1, d_rnn) | x_prev (B, 1, D)
    #                        | Mamba-2 conv tail (B, width-1, conv channels)


class RecLayer(NamedTuple):
    """One layer's view of a stacked recurrent state inside a decode
    step's layer scan: the whole stacks ride the scan's carry and the
    layer writes its own slice in place (the recurrent twin of
    :class:`repro.model.attention.KVLayer`)."""

    h: jax.Array           # (L, B, ...) stacked RecState.h
    conv: jax.Array        # (L, B, width-1, channels) stacked RecState.conv
    layer: jax.Array       # () int32


# ==========================================================================
# RG-LRU (RecurrentGemma)
# ==========================================================================

def init_rglru_block(mk, cfg, name: str):
    d, dr, w = cfg.d_model, cfg.d_rnn, cfg.conv_width
    return {
        "w_y": mk(f"{name}.w_y", (d, dr), ("embed", "rnn")),
        "w_x": mk(f"{name}.w_x", (d, dr), ("embed", "rnn")),
        "conv_w": mk(f"{name}.conv_w", (w, dr), ("taps", "rnn"), "normal", 0.1),
        "gate_a": mk(f"{name}.gate_a", (dr, dr), ("embed", "rnn")),
        "gate_x": mk(f"{name}.gate_x", (dr, dr), ("embed", "rnn")),
        "log_lambda": mk(f"{name}.log_lambda", (dr,), ("rnn",), "normal", 0.5),
        "w_out": mk(f"{name}.w_out", (dr, d), ("rnn", "embed")),
    }


def _rglru_gates(params, xb):
    r = jax.nn.sigmoid(xb @ params["gate_a"])
    i = jax.nn.sigmoid(xb @ params["gate_x"])  # gates gathered by caller
    log_a = -_RGLRU_C * jax.nn.softplus(params["log_lambda"]) * r
    a = jnp.exp(log_a)
    gated_x = i * xb
    # sqrt(1 - a^2) normalizer keeps the state variance bounded.
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.square(a), 1e-6)) * gated_x
    return a, b


def apply_rglru_block(params, x: jax.Array, cfg, *, state: RecState | None = None,
                      token_mask: jax.Array | None = None):
    """x: (B, T, D) -> ((B, T, D), new_state_or_None).

    ``token_mask`` (B, T) bool (stateful calls): masked tokens are state
    no-ops — the recurrence sees (a=1, b=0) there, so ``h`` carries
    through unchanged, and the conv tail is gathered at each request's
    last *valid* tokens.  Must be a prefix mask per row.
    """
    b_, t, _ = x.shape
    g_ = cfg.fsdp_gather_weights
    w_y = gather_for_use(params["w_y"], ("embed", "rnn"), g_)
    w_x = gather_for_use(params["w_x"], ("embed", "rnn"), g_)
    y = jax.nn.gelu(x @ w_y, approximate=True)                  # gate branch
    xb = x @ w_x                                                # recurrent branch
    xb = constrain(xb, "batch", "seq", "rnn")

    # Temporal conv (width 4): the token-shift elevator chain.
    if state is not None:
        ext = jnp.concatenate([state.conv.astype(xb.dtype), xb], axis=1)
        xb_conv = token_shift(ext, params["conv_w"])[:, state.conv.shape[1]:]
        conv_tail = ext[:, ext.shape[1] - (cfg.conv_width - 1):]
    else:
        xb_conv = token_shift(xb, params["conv_w"])
        conv_tail = xb[:, t - (cfg.conv_width - 1):] if t >= cfg.conv_width - 1 else None

    gate_params = {
        "gate_a": gather_for_use(params["gate_a"], ("embed", "rnn"), g_),
        "gate_x": gather_for_use(params["gate_x"], ("embed", "rnn"), g_),
        "log_lambda": params["log_lambda"],
    }
    a, bb = _rglru_gates(gate_params, xb_conv)
    a32, b32 = a.astype(jnp.float32), bb.astype(jnp.float32)
    if token_mask is not None and state is not None:
        # Masked tokens are identity steps: h passes through, so h[:, -1]
        # is each request's state at its last valid token.
        m = token_mask[:, :, None]
        a32 = jnp.where(m, a32, 1.0)
        b32 = jnp.where(m, b32, 0.0)
    h0 = state.h.astype(jnp.float32) if state is not None else None
    # Stateful (serving) calls dispatch the persistent-state decode path
    # (kernels/elevator_scan/decode): h rides a VMEM carry across the
    # window's tokens instead of round-tripping HBM per token.
    h32 = elevator_scan(a32, b32, h0, decode=state is not None)
    h = h32.astype(x.dtype)

    new_state = None
    if state is not None:
        if token_mask is not None:
            # Conv tail at each request's last valid tokens: rows
            # counts..counts+width-2 of [old tail | window] — all-False
            # rows keep the old tail verbatim.
            counts = jnp.sum(token_mask, axis=1, dtype=jnp.int32)
            idx = counts[:, None] + jnp.arange(cfg.conv_width - 1,
                                               dtype=jnp.int32)[None]
            conv_tail = jnp.take_along_axis(ext, idx[:, :, None], axis=1)
        # State read off the f32 scan output (not the model-dtype cast):
        # a frozen slot must round-trip bit-identically even under bf16.
        new_state = RecState(h=h32[:, -1], conv=conv_tail)
    out = (h * y) @ gather_for_use(params["w_out"], ("rnn", "embed"), g_)
    return constrain(out, "batch", "seq", "act_embed"), new_state


# ==========================================================================
# RWKV6 (Finch)
# ==========================================================================

RWKV_HEAD_DIM = 64


def init_rwkv_block(mk, cfg, name: str):
    d = cfg.d_model
    return {
        "mu": mk(f"{name}.mu", (5, d), ("taps", "embed"), "normal", 0.2),
        "w_r": mk(f"{name}.w_r", (d, d), ("embed", "heads_out")),
        "w_k": mk(f"{name}.w_k", (d, d), ("embed", "heads_out")),
        "w_v": mk(f"{name}.w_v", (d, d), ("embed", "heads_out")),
        "w_g": mk(f"{name}.w_g", (d, d), ("embed", "heads_out")),
        # Data-dependent decay (the Finch signature): base + low-rank delta.
        "w_decay_base": mk(f"{name}.w_decay_base", (d,), ("heads_out",), "normal", 0.5),
        "w_decay_lora_a": mk(f"{name}.w_decay_a", (d, 64), ("embed", None)),
        "w_decay_lora_b": mk(f"{name}.w_decay_b", (64, d), (None, "heads_out")),
        "u_bonus": mk(f"{name}.u_bonus", (d,), ("heads_out",), "normal", 0.3),
        "w_o": mk(f"{name}.w_o", (d, d), ("heads_out", "embed")),
        "out_norm": init_rmsnorm(mk, d, f"{name}.out_norm"),
    }


def _rwkv_mix(x, x_prev, mu_row):
    """Token-shift lerp: x + (shift(x) - x) * mu  (Δ=1 elevator edge)."""
    shifted = jnp.concatenate([x_prev, x[:, :-1]], axis=1)
    return x + (shifted - x) * mu_row


# Back-compat aliases: the WKV math now lives with its Pallas kernel in
# repro.kernels.wkv.ref (wkv_sequential_ref is re-exported above).
_wkv_chunked = wkv_chunked_ref


def _wkv_head_plan(b: int, h: int, use_kernel: bool | None):
    """(mesh, batch_axes, head_axes) when the WKV kernel runs under a
    multi-device sharding context, else None.

    The compiler cannot partition a Mosaic kernel: on more than one
    device the kernel runs per (batch, head) block inside a shard_map
    (:func:`~repro.kernels.wkv.seqpar.wkv_headshard`), batch and heads
    mapped as the active rules map them where they divide evenly.  The
    jnp path partitions like any other op and needs no plan.
    """
    ctx = active_context()
    kernel = on_tpu() if use_kernel is None else use_kernel
    if ctx is None or not kernel or ctx[0].size == 1:
        return None
    mesh, rules = ctx

    def fit(axes, n):
        return axes if axes and n % axes_size(mesh, axes) == 0 else None

    return mesh, fit(rules.get("batch"), b), fit(rules.get("act_heads"), h)


def apply_rwkv_block(params, x: jax.Array, cfg, *, state: RecState | None = None,
                     chunk: int = 16, use_kernel: bool | None = None,
                     token_mask: jax.Array | None = None):
    """x: (B, T, D) -> ((B, T, D), new_state_or_None).

    ``token_mask`` (B, T) bool (stateful calls): masked tokens are state
    no-ops — the WKV recurrence sees (w=1, k=0) there, so S carries
    through unchanged on every backend (chunked, decode, seq-parallel)
    without touching the kernels, and the token-shift state is gathered
    at each request's last *valid* token.  Must be a prefix mask per row.
    """
    b, t, d = x.shape
    h = d // RWKV_HEAD_DIM
    dh = RWKV_HEAD_DIM

    x_prev = (
        state.conv.astype(x.dtype)
        if state is not None
        else jnp.zeros((b, 1, d), x.dtype)
    )
    mu = params["mu"]
    xr = _rwkv_mix(x, x_prev, mu[0])
    xk = _rwkv_mix(x, x_prev, mu[1])
    xv = _rwkv_mix(x, x_prev, mu[2])
    xg = _rwkv_mix(x, x_prev, mu[3])
    xw = _rwkv_mix(x, x_prev, mu[4])

    gg = cfg.fsdp_gather_weights
    r = xr @ gather_for_use(params["w_r"], ("embed", "heads_out"), gg)
    k = xk @ gather_for_use(params["w_k"], ("embed", "heads_out"), gg)
    v = xv @ gather_for_use(params["w_v"], ("embed", "heads_out"), gg)
    g = jax.nn.silu(xg @ gather_for_use(params["w_g"], ("embed", "heads_out"), gg))
    # Data-dependent decay in (0, 1): exp(-exp(...)) (Finch).  The logit is
    # clamped so |log w| <= 4: the decay-ratio trick (kernels/wkv) holds
    # per-chunk decay products in fp32, which stays finite iff
    # chunk * |log w| < ~80 (chunk=16 below -> max exponent 64).
    decay_logit = params["w_decay_base"] + (
        jax.nn.tanh(xw @ params["w_decay_lora_a"]) @ params["w_decay_lora_b"]
    )
    decay_logit = jnp.clip(decay_logit.astype(jnp.float32), -6.0, 1.386)
    w = jnp.exp(-jnp.exp(decay_logit))

    def heads(z):
        return z.reshape(b, t, h, dh).swapaxes(1, 2)  # (B,H,T,Dh)

    r_, k_, v_, w_ = heads(r), heads(k), heads(v), heads(w.astype(x.dtype))
    if token_mask is not None and state is not None:
        # Masked tokens are identity steps for S: decay 1, zero k^T v.
        m = token_mask[:, None, :, None]                # (B, 1, T, 1)
        w_ = jnp.where(m, w_, jnp.ones((), w_.dtype))
        k_ = jnp.where(m, k_, jnp.zeros((), k_.dtype))
    u = params["u_bonus"].reshape(h, dh)

    h0 = (
        state.h.astype(jnp.float32)
        if state is not None
        else jnp.zeros((b, h, dh, dh), jnp.float32)
    )
    # Fused WKV elevator kernel: the (Dh, Dh) state rides a VMEM carry.
    # use_kernel=None is auto mode (the elevator_scan convention): the
    # kernel on TPU — for training too, since the custom VJP pairs it with
    # the reverse VMEM-adjoint sweep (kernels/wkv/bwd.py) — and the jnp
    # chunked path elsewhere.  Stateful (serving) calls set decode=True:
    # windows up to DECODE_WINDOW_MAX tokens take the persistent-state
    # decode kernels (kernels/wkv/decode — one HBM round-trip of S per
    # window, no chunk-divisibility constraint), longer cache-fill sweeps
    # fall through to the chunked kernel.  r/k/v/w go in the model dtype
    # (bf16 allowed): every backend accumulates in f32 internally and
    # returns out in the input dtype, so there is no caller-side upcast
    # doubling the kernel's HBM I/O.
    #
    # Under sequence-parallel rules (seq mapped to a mesh axis, e.g. the
    # prefill_seq mode) the WKV dispatches through the shard_map-ed
    # segment-summary path: each device runs the fused kernel on its
    # sequence shard and only the O(Dh²) (decay, state) summary crosses
    # the seq axis — device-space elevator edges instead of a state
    # all-gather (kernels/wkv/seqpar).
    seq_info = seq_shard_info()
    seq_plan = None
    if seq_info is not None and t > 1:
        mesh, seq_ax, batch_ax = seq_info
        n_seq = axes_size(mesh, seq_ax)
        n_b = axes_size(mesh, batch_ax)
        if (isinstance(seq_ax, str) and n_seq > 1 and t % n_seq == 0
                and b % n_b == 0):
            seq_plan = (mesh, seq_ax, batch_ax)
    if seq_plan is not None:
        mesh, seq_ax, batch_ax = seq_plan
        out, S = wkv_seqshard(
            r_, k_, v_, w_, u, h0,
            mesh=mesh, seq_axis=seq_ax, batch_axis=batch_ax,
            chunk=chunk, use_kernel=use_kernel,
        )
    else:
        wkv_kw = dict(
            chunk=chunk,
            use_kernel=use_kernel,
            decode=state is not None,
            # Per-config warn dedup: two configs sharing an awkward
            # (T, chunk) each get their own chunk-adjustment warning.
            warn_scope=getattr(cfg, "name", None),
        )
        head_plan = _wkv_head_plan(b, h, use_kernel)
        if head_plan is None:
            out, S = wkv_fused(r_, k_, v_, w_, u, h0, **wkv_kw)
        else:
            mesh, batch_ax, head_ax = head_plan
            out, S = wkv_headshard(
                r_, k_, v_, w_, u, h0,
                mesh=mesh, batch_axis=batch_ax, head_axis=head_ax, **wkv_kw,
            )

    out = out.swapaxes(1, 2).reshape(b, t, d).astype(x.dtype)
    out = rms_norm(params["out_norm"], out, cfg.norm_eps) * g
    out = out @ gather_for_use(params["w_o"], ("heads_out", "embed"), gg)

    new_state = None
    if state is not None:
        if token_mask is None:
            conv = x[:, -1:]
        else:
            # Token-shift state = each request's last valid token (row
            # counts of [x_prev | x]); an all-False row keeps x_prev.
            counts = jnp.sum(token_mask, axis=1, dtype=jnp.int32)
            ext = jnp.concatenate([x_prev, x], axis=1)
            conv = jnp.take_along_axis(ext, counts[:, None, None], axis=1)
        new_state = RecState(h=S, conv=conv)
    return constrain(out, "batch", "seq", "act_embed"), new_state


# ==========================================================================
# Mamba-2 (SSD)
# ==========================================================================

def init_mamba2_block(mk, cfg, name: str):
    """Mamba-2 mixer.  The published input projection is three leaves:
    ``w_z`` to the gate (d_inner), ``w_xbc`` to the conv channels x | B | C
    (d_inner + 2 d_state) and ``w_dt`` to dt (one per head), each output
    width a whole number of 128 lanes where the published one (8512) is
    not, so no step relays the weight out.  ``conv_w[k]`` weighs the input
    k tokens back.  The per-head ``A_log``, ``dt_bias`` and ``D`` and the
    conv bias are (1, n) rows, not vectors."""
    d, di, nh = cfg.d_model, cfg.mamba_d_inner, cfg.mamba_heads
    cd = cfg.mamba_conv_dim
    row = lambda n, tag: mk(f"{name}.{tag}", (1, n), (None, None),  # noqa: E731
                            "normal", 1.0)
    return {
        "w_z": mk(f"{name}.w_z", (d, di), ("embed", None)),
        "w_xbc": mk(f"{name}.w_xbc", (d, cd), ("embed", None)),
        "w_dt": mk(f"{name}.w_dt", (d, nh), ("embed", None)),
        "conv_w": mk(f"{name}.conv_w", (cfg.conv_width, cd), ("taps", None),
                     "normal", cfg.conv_width ** -0.5),
        "conv_b": row(cd, "conv_b"),
        "dt_bias": row(nh, "dt_bias"),
        "A_log": row(nh, "A_log"),
        "D": row(nh, "D"),
        "norm": init_rmsnorm(mk, di, f"{name}.norm"),
        "w_out": mk(f"{name}.w_out", (di, d), (None, "embed")),
    }


def apply_mamba2_block(params, x: jax.Array, cfg, *,
                       state: RecState | RecLayer | None = None,
                       token_mask: jax.Array | None = None,
                       use_kernel: bool | None = None):
    """x: (B, T, D) -> ((B, T, D), new_state_or_None).

        z, xBC, dt = x W_z, x W_xbc, x W_dt;  xBC = silu(conv4(xBC) + conv_b)
        x, B, C = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(A dt_t) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
        out = rmsnorm(y * silu(z)) W_out

    ``token_mask`` (B, T) bool (stateful calls): a masked token has
    ``dt = 0``, an identity step of the recurrence, and the conv tail is
    gathered at each request's last valid tokens.  Must be a prefix mask.
    A row with no valid token is skipped by the decode-window kernel: its
    state is not touched and its SSD output is zero.
    ``state`` may be a :class:`RecLayer`: the layer's slices of the
    stacked state are read and written in place, and a ``RecLayer``
    comes back.
    Stateful calls take the SSD kernels on TPU (the decode-window kernel
    up to ``DECODE_WINDOW_MAX`` tokens, the chunk kernel beyond); the
    stateless forward takes the jnp path unless ``use_kernel`` says.
    """
    b, t, _ = x.shape
    nh, hp, n = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state
    di, width = cfg.mamba_d_inner, cfg.conv_width
    f32 = jnp.float32
    z, xbc, dt_raw = (x @ params[w] for w in ("w_z", "w_xbc", "w_dt"))

    stacked = isinstance(state, RecLayer)
    if state is None:
        hist = jnp.zeros((b, width - 1, xbc.shape[-1]), xbc.dtype)
    elif stacked:
        hist = jax.lax.dynamic_index_in_dim(state.conv, state.layer,
                                            keepdims=False)
    else:
        hist = state.conv.astype(xbc.dtype)
    ext = jnp.concatenate([hist, xbc], axis=1)
    w = params["conv_w"].astype(f32)
    conv = sum(w[k] * ext[:, width - 1 - k:width - 1 - k + t].astype(f32)
               for k in range(width)) + params["conv_b"].astype(f32)
    xbc = jax.nn.silu(conv).astype(x.dtype)
    xs, bm, cm = jnp.split(xbc, [di, di + n], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(f32) + params["dt_bias"].astype(f32))
    live = None
    if token_mask is not None and state is not None:
        dt = jnp.where(token_mask[:, :, None], dt, 0.0)
        live = jnp.any(token_mask, axis=1)
    a = -jnp.exp(params["A_log"].astype(f32)[0])                # (H,)
    h0 = (state.h if state is not None
          else jnp.zeros((b, nh, hp, n), f32))
    if use_kernel is None:
        use_kernel = on_tpu() and state is not None
    xh = xs.reshape(b, t, nh, hp)
    with jax.named_scope("model.mamba.ssd"):
        y, s_new = ssd(xh, dt, a, bm, cm, h0,
                       state.layer if stacked else None, live,
                       decode=state is not None, use_kernel=use_kernel)
    y = y.astype(f32) + params["D"].astype(f32)[0, :, None] * xh.astype(f32)
    g = y.reshape(b, t, di) * jax.nn.silu(z.astype(f32))
    out = rms_norm(params["norm"], g, cfg.norm_eps).astype(x.dtype)
    out = out @ params["w_out"]

    new_state = None
    if state is not None:
        if token_mask is None:
            tail = ext[:, t:]
        else:
            counts = jnp.sum(token_mask, axis=1, dtype=jnp.int32)
            idx = counts[:, None] + jnp.arange(width - 1, dtype=jnp.int32)[None]
            tail = jnp.take_along_axis(ext, idx[:, :, None], axis=1)
        if stacked:
            new_state = RecLayer(s_new, jax.lax.dynamic_update_index_in_dim(
                state.conv, tail.astype(state.conv.dtype), state.layer, 0),
                state.layer)
        else:
            new_state = RecState(h=s_new, conv=tail)
    return constrain(out, "batch", "seq", "act_embed"), new_state
