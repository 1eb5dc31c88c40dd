"""Logical-axis sharding: one naming scheme, per-mode mesh rules.

Every parameter/activation dimension carries a *logical* name; a rules table
maps logical names to mesh axes per execution mode (train / prefill /
decode).  Model code annotates with :func:`constrain`; the launcher installs
the (mesh, rules) context.  Outside a context everything is a no-op, so the
same model code runs on 1 CPU device and on the 512-chip production mesh.

Parameter construction uses the ``mk`` protocol: every ``init_*`` function
receives a constructor ``mk(name, shape, axes, init)`` and is interpreted
three ways — real arrays (init), ShapeDtypeStructs (abstract, for the
dry-run), or PartitionSpecs (sharding) — from a single code path, so specs
can never drift from shapes.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# --------------------------------------------------------------------------
# Rules: logical axis -> mesh axis (or tuple, or None)
# --------------------------------------------------------------------------

def make_rules(mesh: Mesh, mode: str) -> dict:
    """Sharding rules for a mesh with ("pod",)? + ("data", "model") axes."""
    axes = mesh.axis_names
    data_axes = tuple(a for a in ("pod", "data") if a in axes)
    data = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)
    model = "model" if "model" in axes else None

    rules = {
        # parameters
        "vocab": model,
        "embed": data,        # FSDP/ZeRO-3: weights' d_model axis over data
        "heads_out": model,   # flattened n_heads*head_dim projection dim
        "kv_out": model,
        "ff": model,
        "experts": model,     # EP
        "rnn": model,
        "layers": None,
        "taps": None,
        "stats": None,
        # activations
        "batch": data,
        "seq": None,
        "act_embed": None,
        "act_ff": model,
        "act_heads": model,
        "kv_seq": None,
        "expert_cap": None,
    }
    if mode == "decode":
        # Batched decode: batch over data, KV sequence over model — the
        # cache dominates memory and attention reads it once per step, so
        # seq-sharding it turns decode attention into per-shard partials +
        # an LSE psum (flash-decoding) instead of a KV all-gather.
        rules["kv_seq"] = model
    elif mode == "decode_long":
        # batch=1: KV sequence sharded over *all* axes; batch unshardable.
        both = tuple(a for a in (data if isinstance(data, tuple) else (data,))
                     if a) + ((model,) if model else ())
        rules["batch"] = None
        rules["kv_seq"] = both if len(both) > 1 else (both[0] if both else None)
        rules["seq"] = None
    elif mode == "prefill":
        rules["seq"] = None
    elif mode == "prefill_seq":
        # Long-context prefill: the *sequence* goes over the model axis.
        # Recurrent blocks detect this rule (see seq_shard_info) and take
        # the sequence-parallel WKV path — only the O(Dh²) (decay, state)
        # segment summary crosses the seq axis (kernels/wkv/seqpar), never
        # the token activations the default GSPMD lowering would gather.
        # With the model axis spent on the sequence, the per-token feature
        # activations lose their model mapping (one spec cannot map an
        # axis twice); parameters keep theirs and GSPMD re-gathers them at
        # use — at long-context prompt lengths the activations dominate.
        rules["seq"] = model
        rules["act_ff"] = None
        rules["act_heads"] = None
    return rules


# --------------------------------------------------------------------------
# Context
# --------------------------------------------------------------------------

class _Ctx(threading.local):
    mesh: Mesh | None = None
    rules: dict | None = None


_CTX = _Ctx()


@contextlib.contextmanager
def sharding_context(mesh: Mesh, rules: dict):
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def axes_size(mesh: Mesh, axes) -> int:
    """Total mesh extent of a rules entry (axis name, tuple of names, or
    None/empty → 1)."""
    if not axes:
        return 1
    axes = axes if isinstance(axes, tuple) else (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def active_context():
    """(mesh, rules) of the active sharding context; None outside one."""
    if _CTX.mesh is None or _CTX.rules is None:
        return None
    return _CTX.mesh, _CTX.rules


def seq_shard_info():
    """(mesh, seq_axes, batch_axes) when the active rules map ``seq`` to a
    mesh axis (sequence-parallel mode, e.g. ``prefill_seq``); None
    otherwise.  Recurrent blocks consult this to dispatch the
    segment-summary sequence-parallel path."""
    if _CTX.mesh is None or _CTX.rules is None:
        return None
    seq = _CTX.rules.get("seq")
    if not seq:
        return None
    return _CTX.mesh, seq, _CTX.rules.get("batch")


def to_pspec(axes: tuple, rules: dict) -> P:
    parts = []
    used: set = set()
    for ax in axes:
        r = rules.get(ax) if ax is not None else None
        # A spec may map each mesh axis to at most one dimension.  When a
        # rules mode aliases two logical axes onto the same mesh axis
        # (e.g. prefill_seq maps ``seq`` to the model axis, which ``vocab``
        # also names), the earlier dimension keeps the mapping and later
        # ones replicate — for activation specs the sequence/batch dims
        # come first, which is exactly the priority sequence-parallel
        # modes want.
        vals = r if isinstance(r, tuple) else (r,)
        if any(v in used for v in vals if v is not None):
            r = None
        else:
            used.update(v for v in vals if v is not None)
        parts.append(r)
    # Trim trailing Nones for tidiness.
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def gather_for_use(w: jax.Array, axes: tuple, enabled: bool) -> jax.Array:
    """ZeRO-3 weight gathering: re-constrain a parameter with its data-mesh
    (FSDP) axes stripped, forcing an all-gather of the weight shard before
    use.  Without this GSPMD may instead contract against the sharded dim
    and all-reduce the (much larger) activations.  Model-axis (TP/EP)
    sharding is preserved."""
    if not enabled or _CTX.mesh is None or _CTX.rules is None:
        return w
    data_axes = {a for a in ("pod", "data") if a in _CTX.mesh.axis_names}

    def keep(ax):
        r = _CTX.rules.get(ax) if ax is not None else None
        vals = r if isinstance(r, tuple) else (r,)
        if any(v in data_axes for v in vals if v is not None):
            return None  # strip the FSDP mapping -> gathered at use
        return ax

    axes = tuple(keep(a) for a in axes[-w.ndim:])
    if len(axes) < w.ndim:
        axes = (None,) * (w.ndim - len(axes)) + axes  # leading stack dims
    return constrain(w, *axes)


def constrain(x: jax.Array, *axes: str | None) -> jax.Array:
    """Attach a sharding constraint using the active context (no-op outside)."""
    if _CTX.mesh is None or _CTX.rules is None:
        return x
    spec = to_pspec(axes, _CTX.rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))


# --------------------------------------------------------------------------
# The mk protocol
# --------------------------------------------------------------------------

def init_mk(key: jax.Array, dtype) -> Callable:
    """Real-array constructor; splits the key per call (order-deterministic)."""
    counter = [0]

    def mk(name, shape, axes, init="normal", scale=None):
        counter[0] += 1
        sub = jax.random.fold_in(key, counter[0])
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init == "ones":
            return jnp.ones(shape, dtype)
        if init == "normal":
            s = scale if scale is not None else (shape[0] ** -0.5 if len(shape) > 1 else 0.02)
            return (jax.random.normal(sub, shape, jnp.float32) * s).astype(dtype)
        raise ValueError(init)

    return mk


def abstract_mk(dtype) -> Callable:
    """ShapeDtypeStruct constructor (dry-run: no allocation)."""

    def mk(name, shape, axes, init="normal", scale=None):
        return jax.ShapeDtypeStruct(shape, dtype)

    return mk


def spec_mk(rules: dict) -> Callable:
    """PartitionSpec constructor (same code path as init => always in sync)."""

    def mk(name, shape, axes, init="normal", scale=None):
        return to_pspec(axes, rules)

    return mk
