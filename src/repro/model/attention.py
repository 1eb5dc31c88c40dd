"""Attention: GQA with RoPE/M-RoPE, full/local windows, KV-cache decode.

Train/prefill use the flash-attention Pallas kernel (jnp oracle on CPU);
decode uses a jnp path whose KV-sequence axis may be sharded — softmax over
the sharded axis lowers to the flash-decoding log-sum-exp combine under
GSPMD (partial max/sum per shard + small cross-shard reductions), i.e. the
point-to-point pattern rather than a KV all-gather.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.kv_insert.ops import LANES, write_rows
from repro.kernels.local_attention.ops import flash_attention
from repro.model.layers import apply_rope, init_rmsnorm, rms_norm
from repro.model.sharding import active_context, constrain, gather_for_use


class KVCache(NamedTuple):
    k: jax.Array          # (B, Hkv, S, Dh)
    v: jax.Array          # (B, Hkv, S, Dh)
    length: jax.Array     # (B,) int32 — tokens filled per request (a scalar
    #                       broadcasts: every request at the same position,
    #                       the lockstep special case)


class KVLayer(NamedTuple):
    """Layer ``layer`` of a stacked dense cache, as one decode step's
    attention layer sees it: the whole ``(L, B, Hkv, S, Dh)`` buffers, so
    that the insert writes only its rows of them, in place
    (``transformer.apply_stack`` carries them through its layer scan)."""

    k: jax.Array          # (L, B, Hkv, S, Dh)
    v: jax.Array          # (L, B, Hkv, S, Dh)
    length: jax.Array     # (B,) int32 — this layer's fill per request
    layer: jax.Array | int   # () int32


def writes_in_place(cache) -> bool:
    """A dense cache on one device whose sequence axis is a whole number
    of 128-lane blocks takes the in-place insert
    (:func:`_insert_in_place`).  Any other cache keeps
    :func:`_masked_insert`'s whole-cache rewrite: under a mesh that is
    what keeps each shard of a sequence-sharded cache writing only its
    own slots."""
    ctx = active_context()
    return (isinstance(cache, KVCache) and cache.k.shape[-2] % LANES == 0
            and (ctx is None or ctx[0].size == 1))


#: Physical page 0 of every page pool is the *null page*: never allocated,
#: never written (unmapped logical pages scatter with index -1 / mode
#: "drop", and gathers clip unmapped entries here), so it stays exactly
#: zero for the life of the pool — a masked read of an unmapped slot sees
#: the same zeros a dense cache's never-written slot holds.
NULL_PAGE = 0


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """A KV cache stored as pooled fixed-size pages + a per-slot page table.

    The dense cache's ``(B, Hkv, S, Dh)`` sequence axis becomes
    indirection: logical ring slot ``i`` (= position mod ``s_view``) of
    request ``b`` lives at offset ``i % page_size`` of physical page
    ``page_table[b, i // page_size]``.  ``s_view`` is *exactly* the
    sequence extent the dense cache would have had (``max_len``, or the
    local ring ``min(max_len, window + insert_window - 1)``) — the last
    logical page may be partial — so the gathered view has the dense
    cache's shape and valid content, the positional masks in
    :func:`_decode_attention` apply unchanged, and token streams are
    bit-identical to the dense engine.  Freed/unmapped pages are
    unreachable by construction: an unmapped table entry is ``-1``, whose
    gather clips to the all-zero :data:`NULL_PAGE`, and every slot a
    stale page could alias maps to an absolute position the masks
    already reject.

    ``s_view`` and ``page_size`` are pytree aux data (static at trace
    time); the arrays are the children, so the cache rides ``lax.scan``
    stacking, donation, and checkpointing like any NamedTuple state node.
    """

    k: jax.Array           # (P, page_size, Hkv, Dh) pooled pages
    v: jax.Array           # (P, page_size, Hkv, Dh)
    page_table: jax.Array  # (B, NL) int32 physical page ids; -1 = unmapped
    length: jax.Array      # (B,) int32 — tokens filled per request
    s_view: int            # static: dense-equivalent sequence extent
    page_size: int         # static: tokens per page

    def tree_flatten(self):
        return ((self.k, self.v, self.page_table, self.length),
                (self.s_view, self.page_size))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def init_attention(mk, cfg, name: str, *, cross: bool = False):
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads + cfg.head_pad, cfg.num_kv_heads
    p = {
        "wq": mk(f"{name}.wq", (d, nq * hd), ("embed", "heads_out")),
        "wk": mk(f"{name}.wk", (d, nkv * hd), ("embed", "kv_out")),
        "wv": mk(f"{name}.wv", (d, nkv * hd), ("embed", "kv_out")),
        "wo": mk(f"{name}.wo", (nq * hd, d), ("heads_out", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = mk(f"{name}.bq", (nq * hd,), ("heads_out",), "zeros")
        p["bk"] = mk(f"{name}.bk", (nkv * hd,), ("kv_out",), "zeros")
        p["bv"] = mk(f"{name}.bv", (nkv * hd,), ("kv_out",), "zeros")
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(mk, hd, f"{name}.q_norm")
        p["k_norm"] = init_rmsnorm(mk, hd, f"{name}.k_norm")
    return p


def _project_qkv(params, x, x_kv, cfg):
    b, t, _ = x.shape
    s = x_kv.shape[1]
    nq, nkv, hd = cfg.num_heads + cfg.head_pad, cfg.num_kv_heads, cfg.head_dim
    g = cfg.fsdp_gather_weights
    q = x @ gather_for_use(params["wq"], ("embed", "heads_out"), g)
    k = x_kv @ gather_for_use(params["wk"], ("embed", "kv_out"), g)
    v = x_kv @ gather_for_use(params["wv"], ("embed", "kv_out"), g)
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = constrain(q, "batch", "seq", "act_heads")
    k = constrain(k, "batch", "seq", "act_heads")
    v = constrain(v, "batch", "seq", "act_heads")
    q = q.reshape(b, t, nq, hd).swapaxes(1, 2)     # (B, Hq, T, Dh)
    k = k.reshape(b, s, nkv, hd).swapaxes(1, 2)
    v = v.reshape(b, s, nkv, hd).swapaxes(1, 2)
    if cfg.qk_norm:
        q = rms_norm(params["q_norm"], q, cfg.norm_eps)
        k = rms_norm(params["k_norm"], k, cfg.norm_eps)
    return q, k, v


def _softcap(logits, cap):
    return cap * jnp.tanh(logits / cap) if cap else logits


def apply_attention(
    params,
    x: jax.Array,
    cfg,
    *,
    kind: str = "attn",                    # attn | local | global
    positions: jax.Array | None = None,
    causal: bool = True,
    x_kv: jax.Array | None = None,         # cross-attention memory
    kv_cache: KVCache | None = None,       # decode
    token_mask: jax.Array | None = None,   # (B, t) bool — decode validity
):
    """Returns (out, new_kv_cache_or_None).

    ``token_mask`` (decode only) marks which window tokens are real: masked
    tokens are not inserted into the cache and do not advance the
    per-request length, so a finished / empty slot's cache is untouched and
    pad tokens of a ragged prompt never become attendable.
    """
    b, t, _ = x.shape
    cross = x_kv is not None
    src = x_kv if cross else x
    q, k, v = _project_qkv(params, x, src, cfg)

    window = cfg.attn_window if kind == "local" else None
    if positions is None:
        base = jnp.arange(t, dtype=jnp.int32)[None]
        positions = jnp.broadcast_to(base, (b, t))

    if not cross:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)

    new_cache = None
    if kv_cache is not None and not cross:
        # Decode: append this step's K/V (a window of t >= 1 tokens) and
        # attend to the cache.  Local layers use a ring buffer (slot =
        # pos mod S); the mod-arithmetic in _masked_insert is universal
        # because for a full-length cache length + t <= S.  Lengths are
        # per-request: each slot inserts at — and attends from — its own
        # position.
        advance = (
            jnp.int32(t) if token_mask is None
            else jnp.sum(token_mask, axis=1, dtype=jnp.int32)
        )
        if isinstance(kv_cache, PagedKVCache):
            # Page-table indirection: scatter the window into the slots'
            # mapped pages, gather the dense-shaped view back, and run
            # the *same* positional-mask attention — values at every
            # valid slot equal the dense cache's, so the outputs are
            # bit-identical (masked slots contribute exactly-0 weights
            # either way).
            pool_k, pool_v = _paged_insert(kv_cache, k, v, token_mask)
            new_cache = PagedKVCache(
                pool_k, pool_v, kv_cache.page_table,
                kv_cache.length + advance,
                kv_cache.s_view, kv_cache.page_size,
            )
            with jax.named_scope("model.attn.decode"):
                k_cache = _paged_gather(new_cache, pool_k)
                v_cache = _paged_gather(new_cache, pool_v)
        elif isinstance(kv_cache, KVLayer) or writes_in_place(kv_cache):
            # Write only the new rows, into the stacked buffers in place
            # (an unstacked cache is a stack of one).
            stacked = isinstance(kv_cache, KVLayer)
            view = kv_cache if stacked else KVLayer(
                kv_cache.k[None], kv_cache.v[None], kv_cache.length, 0)
            k_all, v_all = _insert_in_place(view, k, v, token_mask)
            k_cache, v_cache = k_all[view.layer], v_all[view.layer]
            length = kv_cache.length + advance
            new_cache = (KVLayer(k_all, v_all, length, view.layer) if stacked
                         else KVCache(k_cache, v_cache, length))
        else:
            k_cache = _masked_insert(kv_cache.k, k, kv_cache.length, token_mask)
            v_cache = _masked_insert(kv_cache.v, v, kv_cache.length, token_mask)
            new_cache = KVCache(k_cache, v_cache, kv_cache.length + advance)
        with jax.named_scope("model.attn.decode"):
            out = _decode_attention(
                q, k_cache, v_cache, kv_cache.length, cfg, window=window
            )
    else:
        out = flash_attention(
            q, k, v,
            causal=causal and not cross,
            window=window,
            use_kernel=None,
        )

    out = out.swapaxes(1, 2).reshape(
        b, t, (cfg.num_heads + cfg.head_pad) * cfg.head_dim
    )
    out = constrain(out, "batch", "seq", "act_heads")
    wo = gather_for_use(params["wo"], ("heads_out", "embed"), cfg.fsdp_gather_weights)
    return out @ wo, new_cache


def _lengths_2d(length: jax.Array, b: int) -> jax.Array:
    """Per-request lengths as (B, 1) int32; a scalar broadcasts (lockstep)."""
    return jnp.broadcast_to(jnp.reshape(length, (-1, 1)), (b, 1))


def _check_window_fits(t: int, s: int):
    """A window wider than the whole ring can never be represented —
    static shapes, so reject at trace time.  (Windows that *fit* the ring
    but exceed the state's insert_window contract are rejected by
    model.decode_step, which knows the layer kinds and max_len.)"""
    if t > s:
        raise ValueError(
            f"decode window of {t} tokens exceeds cache size {s}; build the "
            f"state with init_decode_state(insert_window >= {t})"
        )


def _masked_insert(cache: jax.Array, new: jax.Array, length: jax.Array,
                   token_mask: jax.Array | None = None):
    """Insert `new` (B,H,t,D) at absolute positions length..length+t-1
    along axis 2 — per request: ``length`` is (B,) (or a scalar, which
    broadcasts), ring-buffer aware (slot = pos mod S per request).

    ``token_mask`` (B, t) drops individual window tokens from the insert:
    a masked token writes nothing, so a finished slot's cache — or the pad
    tail of a ragged prompt — stays bit-identical.

    Uses a positional where-mask instead of dynamic_update_slice so the
    cache's sequence sharding is preserved (no gather/dynamic-slice
    resharding under GSPMD) — each shard updates only the slots it owns:
    the eLDST write-once discipline.
    """
    b = cache.shape[0]
    s = cache.shape[2]
    t = new.shape[2]
    _check_window_fits(t, s)
    idx = jnp.arange(s, dtype=jnp.int32)
    # The window token landing on each slot (ring: slot = pos mod S);
    # t <= S guarantees at most one writer per slot.
    off = jnp.mod(idx[None, :] - _lengths_2d(length, b), s)   # (B, S)
    sel = off < t
    if token_mask is not None:
        # Only real tokens write: look up each slot's candidate window
        # token in the mask.
        sel &= jnp.take_along_axis(
            token_mask, jnp.clip(off, 0, t - 1), axis=1
        )
    if t == 1:
        sel &= off == 0
        return jnp.where(sel[:, None, :, None], new.astype(cache.dtype), cache)
    gathered = jnp.take_along_axis(
        new.astype(cache.dtype), jnp.clip(off, 0, t - 1)[:, None, :, None],
        axis=2,
    )
    return jnp.where(sel[:, None, :, None], gathered, cache)


def _insert_in_place(view: KVLayer, k_new: jax.Array, v_new: jax.Array,
                     token_mask: jax.Array | None = None):
    """:func:`_masked_insert` of ``k_new``/``v_new`` (B, Hkv, t, Dh) into
    layer ``view.layer`` of the stacked buffers, writing only what the
    window adds.  Returns the stacked ``(k, v)``.

    One token (a decode step): each request whose token is real writes
    its ``(Hkv, Dh)`` row at ring slot ``length mod S``
    (:func:`repro.kernels.kv_insert.ops.write_rows`); a masked request
    writes nothing.

    A window of ``t`` tokens in which every request that writes starts
    at position 0 (an admission: the admitted rows were just reset, every
    other row is masked): positions ``[0, t)`` of every row are written,
    a masked token's with what it held, in one ``dynamic_update_slice``
    per buffer — sized by ``t``, and traced the same for every ``t``.
    Any other window rewrites the layer through :func:`_masked_insert`.
    """
    b, _, t, _ = k_new.shape
    s = view.k.shape[-2]
    _check_window_fits(t, s)
    length = _lengths_2d(view.length, b)[:, 0]
    if t == 1:
        write = (jnp.ones((b,), bool) if token_mask is None
                 else token_mask[:, 0])
        slot = jnp.where(write, jnp.mod(length, s), s)
        with jax.named_scope("model.attn.insert"):
            return write_rows(view.k, view.v, k_new[:, :, 0], v_new[:, :, 0],
                              slot, view.layer)
    mask = (jnp.ones((b, t), bool) if token_mask is None else token_mask)
    from_zero = jnp.all((length == 0) | ~jnp.any(mask, axis=1))
    news = (k_new, v_new)

    def prefix(caches):
        start = (view.layer, 0, 0, 0, 0)
        out = []
        with jax.named_scope("model.attn.insert"):
            for cache, new in zip(caches, news):
                old = jax.lax.dynamic_slice(cache, start, (1,) + new.shape)
                new = jnp.where(mask[None, :, None, :, None],
                                new[None].astype(cache.dtype), old)
                out.append(jax.lax.dynamic_update_slice(cache, new, start))
        return tuple(out)

    def whole(caches):
        out = []
        for cache, new in zip(caches, news):
            layer = jax.lax.dynamic_index_in_dim(cache, view.layer,
                                                 keepdims=False)
            layer = _masked_insert(layer, new, view.length, token_mask)
            out.append(jax.lax.dynamic_update_index_in_dim(
                cache, layer, view.layer, 0))
        return tuple(out)

    return jax.lax.cond(from_zero, prefix, whole, (view.k, view.v))


def _paged_gather(cache: PagedKVCache, pool: jax.Array) -> jax.Array:
    """Gather a pooled cache into the dense view ``(B, Hkv, s_view, Dh)``.

    Logical ring slot ``i`` reads offset ``i % page_size`` of physical
    page ``page_table[b, i // page_size]``.  Unmapped entries (-1) clip
    to the all-zero :data:`NULL_PAGE`; every such slot is already
    rejected by the positional masks (it would alias a position beyond
    the slot's fill), so the zeros only guarantee finiteness, exactly
    like a dense cache's never-written slots.
    """
    s, ps = cache.s_view, cache.page_size
    b = cache.page_table.shape[0]
    p, _, hkv, dh = pool.shape
    i = jnp.arange(s, dtype=jnp.int32)
    pages = jnp.take(cache.page_table, i // ps, axis=1)        # (B, S)
    flat = jnp.clip(pages, 0) * ps + (i % ps)[None, :]
    out = jnp.take(pool.reshape(p * ps, hkv, dh), flat.reshape(-1), axis=0)
    return out.reshape(b, s, hkv, dh).swapaxes(1, 2)


def _paged_insert(cache: PagedKVCache, k_new: jax.Array, v_new: jax.Array,
                  token_mask: jax.Array | None = None):
    """Paged dual of :func:`_masked_insert`: scatter ``k_new``/``v_new``
    (B, Hkv, t, Dh) at absolute positions ``length..length+t-1`` into the
    slots' mapped pages (ring slot = pos mod ``s_view``, then page-table
    indirection).  Masked or unmapped targets scatter with index -1 /
    ``mode="drop"`` — nothing is written, so a finished slot's pages, a
    shared read-only prefix page (positions below the slot's start
    length are never insert targets), and the null page all stay
    bit-identical.  Returns (new_k_pool, new_v_pool).
    """
    b, hkv, t, dh = k_new.shape
    s, ps = cache.s_view, cache.page_size
    if t > s:
        raise ValueError(
            f"decode window of {t} tokens exceeds paged view size {s}; "
            f"build the state with init_decode_state(insert_window >= {t})"
        )
    pos = _lengths_2d(cache.length, b) + jnp.arange(t, dtype=jnp.int32)[None]
    slot = jnp.mod(pos, s)                                     # (B, t)
    pages = jnp.take_along_axis(cache.page_table, slot // ps, axis=1)
    ok = pages >= 0
    if token_mask is not None:
        ok &= token_mask
    flat = jnp.where(ok, pages * ps + slot % ps, -1).reshape(-1)

    def put(pool, new):
        pf = pool.reshape(-1, hkv, dh)
        src = new.swapaxes(1, 2).reshape(b * t, hkv, dh).astype(pool.dtype)
        pf = pf.at[flat].set(src, mode="drop")
        return pf.reshape(pool.shape)

    return put(cache.k, k_new), put(cache.v, v_new)


def _decode_attention(q, k_cache, v_cache, cur_pos, cfg, *, window=None):
    """Windowed decode attention against a (possibly seq-sharded) KV cache.

    q: (B, Hq, t, Dh) with t >= 1 new tokens; request b's tokens sit at
    absolute positions cur_pos[b]..cur_pos[b]+t-1 (``cur_pos`` (B,) or
    scalar == pre-insert cache length per request; the cache already
    contains the window's K/V).  Softmax over the cache axis is written
    max/exp/sum-explicitly; if `kv_seq` is sharded, GSPMD lowers it to
    per-shard partials + a tiny psum (flash-decoding combine).
    Ring-buffer caches are handled positionally: post-insert, slot i of
    request b holds absolute position last_b - ((last_b - i) mod S) with
    last_b = cur_pos[b] + t - 1.  Queries mask causally *within* the
    window: query j attends only to slots whose absolute position is
    <= cur_pos[b]+j.
    """
    b, hq, t, hd = q.shape
    nkv = k_cache.shape[1]
    group = hq // nkv
    s = k_cache.shape[2]
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(b, nkv, group, t, hd)
    logits = jnp.einsum(
        "bhgtd,bhsd->bhgts", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) * scale
    logits = _softcap(logits, cfg.attn_logit_softcap)

    slot = jnp.arange(s, dtype=jnp.int32)
    cur2 = _lengths_2d(cur_pos, b)                       # (B, 1)
    last = cur2 + t - 1                                  # (B, 1)
    abs_pos = last - jnp.mod(last - slot[None, :], s)    # (B, S): newest pos
    qpos = cur2 + jnp.arange(t, dtype=jnp.int32)[None]   # (B, t)
    valid = (abs_pos[:, None, :] >= 0) & (abs_pos[:, None, :] <= qpos[:, :, None])
    if window is not None:
        valid &= abs_pos[:, None, :] > (qpos[:, :, None] - window)
    valid = valid[:, None, None]                         # (B, 1, 1, t, s)
    logits = jnp.where(valid, logits, -1e30)

    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    p = jnp.where(valid, p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgts,bhsd->bhgtd", p, v_cache.astype(jnp.float32))
    out = out / jnp.maximum(denom, 1e-30)
    return out.reshape(b, hq, t, hd).astype(q.dtype)
