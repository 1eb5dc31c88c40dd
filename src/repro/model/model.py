"""Top-level model: init / abstract / spec params, forward, prefill, decode.

One code path (the ``mk`` protocol) produces real params, ShapeDtypeStructs
(dry-run) and PartitionSpecs (sharding), so they can never drift.

Input conventions per family:
  * text archs: ``tokens (B, S) int32``.
  * vlm (qwen2-vl): ``tokens (B, S)`` + ``frontend_embeds (B, S_f, D)``
    (precomputed patch embeddings, stub frontend) occupying the first S_f
    positions, + M-RoPE ``positions (3, B, S)``.
  * audio enc-dec (seamless): encoder consumes ``frontend_embeds (B, S, D)``
    (precomputed frame embeddings); decoder consumes ``tokens (B, S_dec)``.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.model import transformer as tf
from repro.model.attention import KVCache, PagedKVCache
from repro.model.layers import (
    embed_tokens,
    init_embeddings,
    init_rmsnorm,
    logits_projection,
    rms_norm,
)
from repro.model.recurrent import RWKV_HEAD_DIM, RecState
from repro.model.sharding import abstract_mk, constrain, init_mk, spec_mk, to_pspec


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------------
# Parameter construction (three interpretations of one code path)
# --------------------------------------------------------------------------

def _build_params(cfg, mk_factory):
    p: dict[str, Any] = {
        "tok": init_embeddings(mk_factory(-1), cfg),
        "final_norm": init_rmsnorm(mk_factory(-2), cfg.d_model, "final_norm"),
        "decoder": tf.init_stack(
            mk_factory, cfg, cross=cfg.is_enc_dec, name="dec"
        ),
    }
    if cfg.is_enc_dec:
        import dataclasses

        enc_cfg = dataclasses.replace(cfg, pattern=("attn",), num_experts=0)
        enc_factory = lambda i: mk_factory(10_000 + i)
        p["encoder"] = tf.init_stack(
            enc_factory, enc_cfg, num_layers=cfg.encoder_layers, name="enc"
        )
        p["enc_final_norm"] = init_rmsnorm(
            mk_factory(-3), cfg.d_model, "enc_final_norm"
        )
    return p


def init_params(cfg, key: jax.Array):
    """Real parameters (smoke tests, examples, small-scale training)."""
    def factory(i):
        return init_mk(jax.random.fold_in(key, i % (2**30)), _dtype(cfg))
    return _build_params(cfg, factory)


def abstract_params(cfg):
    """ShapeDtypeStruct tree — dry-run lowering, no allocation."""
    mk = abstract_mk(_dtype(cfg))
    return _build_params(cfg, lambda i: mk)


def param_pspecs(cfg, rules: dict):
    """PartitionSpec tree aligned with the param tree."""
    mk = spec_mk(rules)
    return _build_params(cfg, lambda i: mk)


# --------------------------------------------------------------------------
# Forward (train / prefill)
# --------------------------------------------------------------------------

def forward(
    params,
    cfg,
    tokens: jax.Array | None = None,
    *,
    positions: jax.Array | None = None,
    frontend_embeds: jax.Array | None = None,
    enc_tokens_embeds: jax.Array | None = None,
) -> jax.Array:
    """Returns logits (B, S, V) (decoder logits for enc-dec)."""
    enc_out = None
    if cfg.is_enc_dec:
        assert enc_tokens_embeds is not None, "enc-dec needs encoder inputs"
        import dataclasses

        enc_cfg = dataclasses.replace(cfg, pattern=("attn",), num_experts=0)
        ex = enc_tokens_embeds.astype(_dtype(cfg))
        ex, _ = tf.apply_stack(
            params["encoder"], ex, enc_cfg, causal=False,
            num_layers=cfg.encoder_layers,
        )
        enc_out = rms_norm(params["enc_final_norm"], ex, cfg.norm_eps)

    x = embed_tokens(params["tok"], tokens, cfg)
    if frontend_embeds is not None:
        s_f = frontend_embeds.shape[1]
        x = jnp.concatenate(
            [frontend_embeds.astype(x.dtype), x[:, s_f:]], axis=1
        )
    x = x * jnp.asarray(cfg.embed_scale, x.dtype)

    x, _ = tf.apply_stack(
        params["decoder"], x, cfg, positions=positions, causal=True,
        enc_out=enc_out,
    )
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    return logits_projection(params["tok"], x, cfg)


# --------------------------------------------------------------------------
# Decode state (KV caches / recurrent states), concrete + abstract
# --------------------------------------------------------------------------

class PageSpec(NamedTuple):
    """Geometry of a paged decode state (see
    :class:`repro.model.attention.PagedKVCache`).

    ``page_size``: tokens per physical page — a multiple of the 32-token
    admit bucket, so page boundaries and admission buckets line up.
    ``private_pages``: allocatable (non-shared) physical pages per KV
    node pool; ``None`` = dense-equivalent capacity (``batch`` × logical
    pages per slot), which can never starve.  Each node's pool is capped
    at that dense-equivalent count regardless — a local ring can't use
    more.  ``shared_pages``: extra read-only pages reserved (per
    full-view node) for prefilled shared prefixes.
    """

    page_size: int = 32
    private_pages: int | None = None
    shared_pages: int = 0


def _layer_state_shape(cfg, kind: str, batch: int, max_len: int,
                       insert_window: int = 1, paged: PageSpec | None = None):
    dt = _dtype(cfg)
    if kind in tf.ATTN_KINDS:
        window = cfg.attn_window if kind == "local" else None
        # Local layers only retain a window-sized cache (ring-buffer slots).
        # Multi-token decode windows need insert_window - 1 slack slots so
        # a window inserted at once never overwrites positions its earlier
        # queries still attend to; capped at max_len the ring can't wrap at
        # all, so either way windowed decode stays exact.
        s = min(max_len, window + insert_window - 1) if window else max_len
        if paged is not None:
            ps = int(paged.page_size)
            nl = -(-s // ps)                       # logical pages per slot
            cap = batch * nl                       # dense-equivalent pool
            private = cap if paged.private_pages is None else min(
                int(paged.private_pages), cap)
            # Shared prefix pages only exist where they are immutable:
            # a view spanning every position (s == max_len) never wraps,
            # so pages below a slot's start length are never written.
            shared = int(paged.shared_pages) if s == max_len else 0
            pool = (1 + shared + private, ps, cfg.num_kv_heads, cfg.head_dim)
            return PagedKVCache(
                k=jax.ShapeDtypeStruct(pool, dt),
                v=jax.ShapeDtypeStruct(pool, dt),
                page_table=jax.ShapeDtypeStruct((batch, nl), jnp.int32),
                length=jax.ShapeDtypeStruct((batch,), jnp.int32),
                s_view=s, page_size=ps,
            )
        kv_shape = (batch, cfg.num_kv_heads, s, cfg.head_dim)
        return KVCache(
            k=jax.ShapeDtypeStruct(kv_shape, dt),
            v=jax.ShapeDtypeStruct(kv_shape, dt),
            # Per-request fill counts: continuous batching advances each
            # slot at its own pace (lockstep is the all-equal special case).
            length=jax.ShapeDtypeStruct((batch,), jnp.int32),
        )
    if kind == "rec":
        return RecState(
            h=jax.ShapeDtypeStruct((batch, cfg.d_rnn), jnp.float32),
            conv=jax.ShapeDtypeStruct((batch, cfg.conv_width - 1, cfg.d_rnn), dt),
        )
    if kind == "rwkv":
        h = cfg.d_model // RWKV_HEAD_DIM
        return RecState(
            h=jax.ShapeDtypeStruct((batch, h, RWKV_HEAD_DIM, RWKV_HEAD_DIM), jnp.float32),
            conv=jax.ShapeDtypeStruct((batch, 1, cfg.d_model), dt),
        )
    if kind == "mamba":
        return RecState(
            h=jax.ShapeDtypeStruct((batch, cfg.mamba_heads, cfg.mamba_head_dim,
                                    cfg.mamba_d_state), jnp.float32),
            conv=jax.ShapeDtypeStruct((batch, cfg.conv_width - 1,
                                       cfg.mamba_conv_dim), dt),
        )
    raise ValueError(kind)


def abstract_decode_state(cfg, batch: int, max_len: int,
                          insert_window: int = 1,
                          paged: PageSpec | None = None):
    pattern, n_periods, remainder = tf.plan_groups(cfg)

    def stack(sds_tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_periods,) + s.shape, s.dtype),
            sds_tree,
        )

    scanned = (
        [stack(_layer_state_shape(cfg, k, batch, max_len, insert_window,
                                  paged))
         for k in pattern]
        if n_periods > 0
        else None
    )
    rem = [_layer_state_shape(cfg, k, batch, max_len, insert_window, paged)
           for k in remainder]
    return {"scanned": scanned, "remainder": rem}


def init_decode_state(cfg, batch: int, max_len: int, insert_window: int = 1,
                      paged: PageSpec | None = None):
    """Zeroed decode state.  ``insert_window`` is the widest token window
    any single ``decode_step`` call will insert (1 = classic per-token
    decode) — it sizes the local-attention ring slack; recurrent states
    are O(1) in it.  The WKV state stays (B, H, Dh, Dh) float32 end to
    end: serve loops carry it without per-step reshapes or casts.

    ``paged`` swaps every KV node for a :class:`PagedKVCache` pool of
    that geometry; page tables initialize to -1 (nothing mapped)."""
    state = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        abstract_decode_state(cfg, batch, max_len, insert_window, paged),
    )
    if paged is None:
        return state

    def unmap(node):
        if isinstance(node, PagedKVCache):
            return PagedKVCache(
                node.k, node.v, jnp.full_like(node.page_table, -1),
                node.length, node.s_view, node.page_size,
            )
        return node

    return jax.tree.map(
        unmap, state,
        is_leaf=lambda x: isinstance(x, (KVCache, PagedKVCache, RecState)),
    )


def decode_state_pspecs(cfg, batch: int, max_len: int, rules: dict,
                        insert_window: int = 1,
                        paged: PageSpec | None = None):
    """PartitionSpecs for the decode state.

    KV caches shard (batch, ·, kv_seq, ·); recurrent states shard
    (batch, rnn-ish) — built by walking the typed abstract tree, so stacked
    (leading ``layers``) axes are detected from rank deltas.  Paged pools
    stay replicated (any slot's table may reference any page); their
    tables/lengths shard along batch.
    """
    abstract = abstract_decode_state(cfg, batch, max_len, insert_window,
                                     paged)

    def node_spec(node):
        if isinstance(node, KVCache):
            extra = len(node.k.shape) - 4  # 0 = unstacked, 1 = (L, B, H, S, D)
            prefix = ("layers",) * extra
            kv = to_pspec(prefix + ("batch", None, "kv_seq", None), rules)
            ln = to_pspec(prefix + ("batch",), rules)
            return KVCache(k=kv, v=kv, length=ln)
        if isinstance(node, PagedKVCache):
            extra = len(node.k.shape) - 4
            prefix = ("layers",) * extra
            pool = to_pspec(prefix + (None, None, None, None), rules)
            tbl = to_pspec(prefix + ("batch", None), rules)
            ln = to_pspec(prefix + ("batch",), rules)
            return PagedKVCache(k=pool, v=pool, page_table=tbl, length=ln,
                                s_view=node.s_view, page_size=node.page_size)
        if isinstance(node, RecState):
            extra = len(node.conv.shape) - 3
            prefix = ("layers",) * extra
            h_axes = prefix + ("batch",) + (None,) * (len(node.h.shape) - extra - 1)
            c_axes = prefix + ("batch", None, "rnn")
            return RecState(h=to_pspec(h_axes, rules), conv=to_pspec(c_axes, rules))
        raise TypeError(type(node))

    return jax.tree.map(
        node_spec, abstract,
        is_leaf=lambda x: isinstance(x, (KVCache, PagedKVCache, RecState)),
    )


def decode_state_finite(state) -> jax.Array:
    """(B,) bool — per-slot finiteness of the recurrent decode state.

    Reduces ``isfinite`` over every :class:`RecState` leaf (WKV S, RG-LRU
    h, conv tails) per batch row — the fault-detection flag a serving
    window folds into its jitted scan: a slot whose recurrent state went
    non-finite is quarantined *inside* the jit (no extra dispatch, no
    host sync per token).  KV caches are deliberately not scanned — a
    NaN KV row poisons that slot's logits the same step it is attended,
    so the caller's logits-finiteness check covers attention state at
    O(V) instead of O(max_len·H·Dh) per step.

    Attention-only states (no recurrent layers) return all-True: slot
    health is then carried entirely by the logits check.
    """
    flags = []
    batch = None

    def visit(node):
        nonlocal batch
        if isinstance(node, (KVCache, PagedKVCache)):
            if batch is None:
                batch = node.length.shape[-1]
            return
        if not isinstance(node, RecState):
            raise TypeError(type(node))
        # Leaves are (B, ...) or stacked (L, B, ...): the conv tail's rank
        # relative to its unstacked 3 gives the stacked prefix length,
        # hence the batch axis, for both leaves.
        stacked = node.conv.ndim - 3
        if batch is None:
            batch = node.conv.shape[stacked]
        for leaf in (node.h, node.conv):
            axes = tuple(a for a in range(leaf.ndim) if a != stacked)
            flags.append(jnp.all(jnp.isfinite(leaf), axis=axes))

    jax.tree.map(visit, state,
                 is_leaf=lambda x: isinstance(x, (KVCache, PagedKVCache,
                                                  RecState)))
    if not flags:
        return jnp.ones((batch,), bool)
    return functools.reduce(jnp.logical_and, flags)


def _checksum_words(leaf, batch_axis: int) -> jax.Array:
    """Per-slot uint32 wraparound sum of ``leaf``'s raw bit patterns.

    Bitcast (never value-convert) to unsigned words first: the sum is then
    an exact, order-independent function of the stored bits — modular
    integer addition is associative/commutative, so XLA may reduce in any
    order without changing the result, which a float-valued checksum could
    not guarantee.  A single flipped bit changes one word by a power of
    two, so the slot sum always moves.
    """
    nbytes = jnp.dtype(leaf.dtype).itemsize
    if nbytes >= 4:
        words = jax.lax.bitcast_convert_type(leaf, jnp.uint32)
    else:
        uint = jnp.uint8 if nbytes == 1 else jnp.uint16
        words = jax.lax.bitcast_convert_type(leaf, uint).astype(jnp.uint32)
    axes = tuple(a for a in range(words.ndim) if a != batch_axis)
    return jnp.sum(words, axis=axes, dtype=jnp.uint32)


def decode_state_checksum(state) -> jax.Array:
    """(B,) uint32 — per-slot wraparound checksum of the decode state.

    The silent-corruption complement to :func:`decode_state_finite`: a
    bit flip that leaves a value finite-but-wrong never trips the
    ``isfinite`` quarantine, but it always moves this sum.  Covers every
    per-slot leaf: recurrent states (WKV S / RG-LRU h, conv tails), dense
    KV caches (contents + lengths), and paged KV nodes (each slot's
    *mapped* pool pages gathered through its page table, plus the table
    and length words themselves — so a corrupted mapping is caught even
    when the pool bytes are intact).

    Cost: one O(state bytes) integer reduction per call — a serving
    window computes it twice per K-token dispatch (entry + exit), which
    is small against K forward passes.  Shared prefix pages are included
    in every sharing slot's sum; that keeps the sum a pure function of
    (state, slot) and stays deterministic.
    """
    sums = []
    batch = None

    def paged_sum(pool, tbl):
        # pool (P, ps, Hkv, Dh), tbl (B, nl) -> (B,) uint32 over mapped
        # pages only (unmapped entries are -1; their gather is masked out).
        pages = jnp.take(pool, jnp.clip(tbl, 0), axis=0)
        nbytes = jnp.dtype(pages.dtype).itemsize
        if nbytes >= 4:
            words = jax.lax.bitcast_convert_type(pages, jnp.uint32)
        else:
            uint = jnp.uint8 if nbytes == 1 else jnp.uint16
            words = jax.lax.bitcast_convert_type(pages, uint).astype(
                jnp.uint32)
        per_page = jnp.sum(
            words, axis=tuple(range(2, words.ndim)), dtype=jnp.uint32)
        return jnp.sum(jnp.where(tbl >= 0, per_page, 0), axis=1,
                       dtype=jnp.uint32)

    def visit(node):
        nonlocal batch
        if isinstance(node, KVCache):
            stacked = node.k.ndim - 4
            if batch is None:
                batch = node.length.shape[-1]
            for leaf in (node.k, node.v):
                sums.append(_checksum_words(leaf, stacked))
            sums.append(_checksum_words(node.length, node.length.ndim - 1))
            return
        if isinstance(node, PagedKVCache):
            stacked = node.k.ndim - 4
            if batch is None:
                batch = node.length.shape[-1]
            fn = paged_sum
            for _ in range(stacked):
                fn = jax.vmap(fn)
            for pool in (node.k, node.v):
                s = fn(pool, node.page_table)
                if stacked:
                    s = jnp.sum(s, axis=tuple(range(stacked)),
                                dtype=jnp.uint32)
                sums.append(s)
            sums.append(_checksum_words(node.page_table,
                                        node.page_table.ndim - 2))
            sums.append(_checksum_words(node.length, node.length.ndim - 1))
            return
        if not isinstance(node, RecState):
            raise TypeError(type(node))
        stacked = node.conv.ndim - 3
        if batch is None:
            batch = node.conv.shape[stacked]
        for leaf in (node.h, node.conv):
            sums.append(_checksum_words(leaf, stacked))

    jax.tree.map(visit, state,
                 is_leaf=lambda x: isinstance(x, (KVCache, PagedKVCache,
                                                  RecState)))
    if not sums:
        return jnp.zeros((batch,), jnp.uint32)
    return functools.reduce(jnp.add, sums)


# --------------------------------------------------------------------------
# Decode step
# --------------------------------------------------------------------------

def _check_ring_slack(cfg, state, t: int, max_len: int | None):
    """Trace-time guard for the local-attention ring contract.

    A window of ``t`` tokens inserted into a ring of ``S`` slots is exact
    iff ``S >= attn_window + t - 1`` (the slack ``init_decode_state``
    sizes via ``insert_window``) — or the ring can never wrap at all,
    which the builder guarantees by capping ``S`` at ``max_len``.  Before
    this check, violating the contract silently evicted slots the
    window's earlier queries still attend to (corrupt logits, no error).
    ``max_len=None`` (caller didn't vouch for the cap) treats any
    slack-deficient ring as an error.

    The rule itself lives in :mod:`repro.analysis.ringslack` (one source
    of truth for the trace-time guard and the static audit); this wrapper
    only turns violations into the trace-time ``ValueError``.
    """
    from repro.analysis.ringslack import ring_slack_violations

    msgs = ring_slack_violations(cfg, state, t, max_len)
    if msgs:
        raise ValueError(msgs[0])


def decode_step(params, cfg, state, tokens: jax.Array, lengths: jax.Array,
                *, enc_out: jax.Array | None = None,
                last_only: bool = False,
                token_mask: jax.Array | None = None,
                max_len: int | None = None):
    """One serve step over a window of tokens (B, K), K >= 1, given caches
    filled to ``lengths`` — scalar (lockstep: every request at the same
    position) or per-request ``(B,)``: request b's K tokens occupy
    positions ``lengths[b]..lengths[b]+K-1`` (causal within the window).
    K == 1 is classic per-token decode; K > 1 amortizes dispatch and, on
    the WKV path, the state's HBM round-trip (kernels/wkv/decode).

    ``token_mask`` (B, K) bool marks which window tokens are *real*.
    Masked tokens contribute nothing to any state — KV-cache slots are not
    written, per-request lengths don't advance, and recurrent states carry
    through unchanged (``jnp.where``-frozen) — so an all-False row leaves
    a finished/empty slot's state bit-identical, and a prefix mask
    (``arange(K) < prompt_len``) prefills a ragged prompt without pad
    pollution.  The mask must be a *prefix* per row (valid tokens, then
    padding): recurrent final states are read at the last valid position.

    The state must have been built with
    ``init_decode_state(insert_window >= K)``; a slack-deficient
    local-attention ring now fails at trace time (see
    :func:`_check_ring_slack`) instead of silently corrupting output —
    pass ``max_len`` (the position cap the state was built with) to allow
    rings legitimately capped at ``max_len``.

    ``last_only=True`` projects logits for the window's final *valid*
    position only (per request, when ``token_mask`` is given) — a greedy
    serve loop needs just that, and skipping the other K-1 (or P-1, at
    prefill) vocab projections keeps the logits buffer (B, 1, V) instead
    of (B, K, V).

    Returns (logits (B, K, V) — (B, 1, V) with ``last_only`` — new_state).
    """
    b, t = tokens.shape
    _check_ring_slack(cfg, state, t, max_len)
    lengths = jnp.reshape(jnp.asarray(lengths, jnp.int32), (-1, 1))
    positions = jnp.broadcast_to(
        lengths + jnp.arange(t, dtype=jnp.int32)[None, :], (b, t)
    ).astype(jnp.int32)
    with jax.named_scope("model.embed"):
        x = embed_tokens(params["tok"], tokens, cfg)
        x = x * jnp.asarray(cfg.embed_scale, x.dtype)
    x, new_state = tf.apply_stack(
        params["decoder"], x, cfg, positions=positions, causal=True,
        states=state, enc_out=enc_out, token_mask=token_mask,
    )
    with jax.named_scope("model.head"):
        if last_only:
            if token_mask is None:
                x = x[:, -1:]
            else:
                # Per-request last valid position (clamped: an all-False
                # row yields garbage logits the caller must ignore).
                idx = jnp.clip(
                    jnp.sum(token_mask, axis=1, dtype=jnp.int32) - 1, 0, t - 1
                )
                x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = logits_projection(params["tok"], x, cfg)
    return logits, new_state
