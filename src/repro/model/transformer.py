"""Transformer assembly: pattern-aware blocks, scan-over-periods, enc-dec.

Layer patterns (gemma3 5×local:1×global, recurrentgemma rec:rec:attn) are
handled by scanning over *periods*: one period = one instance of the pattern
with heterogeneous sublayers; params are stacked over periods so the HLO
contains each layer body once (compile time & HLO size stay O(pattern), not
O(num_layers)).  Remainder layers (when the pattern doesn't divide
num_layers) are unrolled individually.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from repro.core.lowering import scan_unroll

from repro.model import attention as attn_mod
from repro.model import moe as moe_mod
from repro.model import recurrent as rec_mod
from repro.model.attention import KVCache
from repro.model.layers import apply_mlp, init_mlp, init_rmsnorm, rms_norm
from repro.model.recurrent import RecState
from repro.model.sharding import constrain

ATTN_KINDS = ("attn", "local", "global")
#: Trace scope of each block kind's sequence mixer (``jax.named_scope``).
MIXER_SCOPES = {"attn": "model.mixer.attn", "local": "model.mixer.attn",
                "global": "model.mixer.attn", "rec": "model.mixer.rglru",
                "rwkv": "model.mixer.rwkv", "mamba": "model.mixer.mamba"}


# --------------------------------------------------------------------------
# Block init / apply
# --------------------------------------------------------------------------

def init_block(mk, cfg, kind: str, name: str, *, cross: bool = False):
    p: dict[str, Any] = {"ln1": init_rmsnorm(mk, cfg.d_model, f"{name}.ln1"),
                         "ln2": init_rmsnorm(mk, cfg.d_model, f"{name}.ln2")}
    if kind in ATTN_KINDS:
        p["attn"] = attn_mod.init_attention(mk, cfg, f"{name}.attn")
    elif kind == "rec":
        p["rec"] = rec_mod.init_rglru_block(mk, cfg, f"{name}.rec")
    elif kind == "rwkv":
        p["rwkv"] = rec_mod.init_rwkv_block(mk, cfg, f"{name}.rwkv")
    elif kind == "mamba":
        p["mamba"] = rec_mod.init_mamba2_block(mk, cfg, f"{name}.mamba")
    else:
        raise ValueError(kind)
    if cross:
        p["ln_cross"] = init_rmsnorm(mk, cfg.d_model, f"{name}.ln_cross")
        p["cross"] = attn_mod.init_attention(mk, cfg, f"{name}.cross", cross=True)
    if cfg.num_experts:
        p["ffn"] = moe_mod.init_moe(mk, cfg, f"{name}.moe")
    else:
        p["ffn"] = init_mlp(mk, cfg, f"{name}.mlp")
    return p


def apply_block(
    params, x, cfg, kind: str, *, positions=None, causal=True,
    state=None, enc_out=None, token_mask=None,
):
    """Pre-norm block. Returns (x, new_state_or_None).

    ``token_mask`` (B, t) bool (decode only): masked tokens leave every
    state leaf untouched — KV slots unwritten, recurrent carries frozen.
    """
    if kind not in MIXER_SCOPES:
        raise ValueError(kind)
    h = rms_norm(params["ln1"], x, cfg.norm_eps)
    with jax.named_scope(MIXER_SCOPES[kind]):
        if kind in ATTN_KINDS:
            out, new_state = attn_mod.apply_attention(
                params["attn"], h, cfg, kind=kind, positions=positions,
                causal=causal, kv_cache=state, token_mask=token_mask,
            )
        elif kind == "rec":
            out, new_state = rec_mod.apply_rglru_block(
                params["rec"], h, cfg, state=state, token_mask=token_mask)
        elif kind == "mamba":
            out, new_state = rec_mod.apply_mamba2_block(
                params["mamba"], h, cfg, state=state, token_mask=token_mask)
        else:
            out, new_state = rec_mod.apply_rwkv_block(
                params["rwkv"], h, cfg, state=state, token_mask=token_mask)
    x = x + _residual(out, cfg)

    if enc_out is not None and "cross" in params:
        h = rms_norm(params["ln_cross"], x, cfg.norm_eps)
        out, _ = attn_mod.apply_attention(
            params["cross"], h, cfg, x_kv=enc_out, causal=False,
        )
        x = x + out

    h = rms_norm(params["ln2"], x, cfg.norm_eps)
    with jax.named_scope("model.mlp"):
        if cfg.num_experts:
            if cfg.moe_impl == "a2a":
                from repro.model.moe_a2a import apply_moe_sharded

                out = apply_moe_sharded(params["ffn"], h, cfg)
            else:
                out = moe_mod.apply_moe(params["ffn"], h, cfg)
        else:
            out = apply_mlp(params["ffn"], h, cfg)
    x = x + _residual(out, cfg)
    return constrain(x, "batch", "seq", "act_embed"), new_state


def _residual(out, cfg):
    """A block branch as it joins the residual stream: scaled by the
    configuration's residual multiplier (granite's 0.22), untouched at
    the default 1."""
    if cfg.residual_multiplier == 1.0:
        return out
    return out * jnp.asarray(cfg.residual_multiplier, out.dtype)


# --------------------------------------------------------------------------
# Layer-group planning
# --------------------------------------------------------------------------

def plan_groups(cfg, num_layers: int | None = None):
    """(pattern, n_periods, remainder_kinds) for scan-over-periods."""
    pattern = cfg.pattern
    n = num_layers if num_layers is not None else cfg.num_layers
    p = len(pattern)
    n_periods = n // p
    remainder = tuple(pattern[i % p] for i in range(n_periods * p, n))
    return pattern, n_periods, remainder


def init_stack(mk_factory, cfg, *, num_layers=None, cross=False, name="dec"):
    """Init scanned period params (stacked over periods) + remainder list.

    ``mk_factory(i)`` returns an mk for period/remainder instance i — for
    real init each instance gets fresh keys; for abstract/spec modes the
    same constructor is reused and leaves are stacked.
    """
    pattern, n_periods, remainder = plan_groups(cfg, num_layers)

    def init_period(mk, tag):
        return [
            init_block(mk, cfg, kind, f"{name}.{tag}.l{j}", cross=cross)
            for j, kind in enumerate(pattern)
        ]

    if n_periods > 0:
        periods = [init_period(mk_factory(i), f"p{i}") for i in range(n_periods)]
        scanned = jax.tree.map(lambda *xs: _stack_leaves(xs), *periods)
    else:
        scanned = None
    rem = [
        init_block(mk_factory(n_periods + i), cfg, kind, f"{name}.r{i}", cross=cross)
        for i, kind in enumerate(remainder)
    ]
    return {"scanned": scanned, "remainder": rem}


def _stack_leaves(leaves):
    first = leaves[0]
    if isinstance(first, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct((len(leaves),) + first.shape, first.dtype)
    if _is_pspec(first):
        # PartitionSpec: prepend the (unsharded) layer axis.
        from jax.sharding import PartitionSpec as P
        return P(None, *first)
    return jnp.stack(leaves)


def _is_pspec(x):
    from jax.sharding import PartitionSpec
    return isinstance(x, PartitionSpec)


def apply_stack(
    stack_params, x, cfg, *, positions=None, causal=True,
    states=None, enc_out=None, num_layers=None, token_mask=None,
):
    """Apply scanned periods + remainder.  Returns (x, new_states_or_None).

    ``states``: {"scanned": stacked-state pytree or None, "remainder": list}.
    ``token_mask``: see :func:`apply_block` (decode-state freezing).
    """
    pattern, n_periods, remainder = plan_groups(cfg, num_layers)
    remat_policy = _remat_policy(cfg)

    def period_fn(x, period_params, period_states):
        new_states = []
        for sub_params, kind, sub_state in zip(
            period_params, pattern,
            period_states if period_states is not None else [None] * len(pattern),
        ):
            x, ns = apply_block(
                sub_params, x, cfg, kind, positions=positions, causal=causal,
                state=sub_state, enc_out=enc_out, token_mask=token_mask,
            )
            new_states.append(ns)
        return x, new_states

    if remat_policy is not None:
        period_fn = jax.checkpoint(period_fn, policy=remat_policy)

    new_scan_states = None
    if n_periods > 0:
        if states is None or states.get("scanned") is None:
            def scan_body(carry, period_params):
                y, _ = period_fn(carry, period_params, None)
                return y, None
            x, _ = jax.lax.scan(
                scan_body, x, stack_params["scanned"], unroll=scan_unroll()
            )
        elif not any(map(_carried, states["scanned"], pattern)):
            def scan_body(carry, inputs):
                period_params, period_states = inputs
                y, ns = period_fn(carry, period_params, period_states)
                return y, ns
            x, new_scan_states = jax.lax.scan(
                scan_body, x, (stack_params["scanned"], states["scanned"]),
                unroll=scan_unroll(),
            )
        else:
            x, new_scan_states = _scan_carrying(
                period_fn, x, stack_params["scanned"], states["scanned"],
                pattern, n_periods)

    new_rem_states = []
    for i, (sub_params, kind) in enumerate(zip(stack_params["remainder"], remainder)):
        st = states["remainder"][i] if states is not None else None
        x, ns = apply_block(
            sub_params, x, cfg, kind, positions=positions, causal=causal,
            state=st, enc_out=enc_out, token_mask=token_mask,
        )
        new_rem_states.append(ns)

    if states is None:
        return x, None
    return x, {"scanned": new_scan_states, "remainder": new_rem_states}


def _carried(state, kind: str) -> str | None:
    """How a layer's stacked decode state rides the layer scan's carry:
    ``"kv"`` for a dense KV cache that takes the in-place insert
    (:func:`repro.model.attention.writes_in_place`), ``"rec"`` for a
    Mamba-2 state (written in place by its layer), else None (scanned as
    ``xs``/``ys``)."""
    if attn_mod.writes_in_place(state):
        return "kv"
    if kind == "mamba":
        return "rec"
    return None


def _scan_carrying(period_fn, x, scanned_params, scanned_states, pattern,
                   n_periods: int):
    """The layer scan of a decode step in which some layers' states are
    written in place (:func:`_carried`).

    Scanned as ``xs`` and rebuilt as ``ys``, each layer's state would be
    copied whole at every step, however little the step wrote, and a
    second copy of the whole state held while it runs.  So those stacked
    buffers ride the carry instead: a dense KV cache's K and V (each
    attention layer gets them with its index, a
    :class:`repro.model.attention.KVLayer`, and writes its rows), a
    Mamba-2 layer's state and conv tail (a
    :class:`repro.model.recurrent.RecLayer`).  KV lengths, and every
    other state, stay ``xs``/``ys``.
    """
    carried = [_carried(s, k) for s, k in zip(scanned_states, pattern)]
    xs_states = [s.length if c == "kv" else None if c else s
                 for s, c in zip(scanned_states, carried)]
    bufs0 = [(s.k, s.v) if c == "kv" else (s.h, s.conv) if c else None
             for s, c in zip(scanned_states, carried)]

    def view(buf, st, c, layer):
        if c == "kv":
            return attn_mod.KVLayer(*buf, st, layer)
        return rec_mod.RecLayer(*buf, layer) if c else st

    def scan_body(carry, inputs):
        y, bufs = carry
        period_params, period_states, layer = inputs
        views = [view(bufs[j], st, c, layer)
                 for j, (st, c) in enumerate(zip(period_states, carried))]
        y, ns = period_fn(y, period_params, views)
        bufs = [(n.k, n.v) if c == "kv" else (n.h, n.conv) if c else None
                for n, c in zip(ns, carried)]
        ys = [n.length if c == "kv" else None if c else n
              for n, c in zip(ns, carried)]
        return (y, bufs), ys

    (x, bufs), ys = jax.lax.scan(
        scan_body, (x, bufs0),
        (scanned_params, xs_states, jnp.arange(n_periods, dtype=jnp.int32)),
        unroll=scan_unroll(),
    )
    return x, [KVCache(*bufs[j], n) if c == "kv" else RecState(*bufs[j]) if c
               else n for j, (n, c) in enumerate(zip(ys, carried))]


def _remat_policy(cfg):
    if cfg.remat == "none":
        return None
    if cfg.remat == "dots":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    if cfg.remat == "full":
        return jax.checkpoint_policies.nothing_saveable
    raise ValueError(cfg.remat)
