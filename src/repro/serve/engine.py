"""Serving: prefill + decode steps, a batched greedy engine, and a
continuous-batching scheduler.

``make_prefill_step`` / ``make_decode_step`` are the lowering targets for
the ``prefill_*`` / ``decode_*`` / ``long_*`` shape cells;
``make_cache_prefill_step`` fills the decode cache from a prompt in one
jit; ``ServeEngine`` drives them for the runnable example with a
windowed, donated-state decode loop — the serving rendering of the
paper's loop-carried-value argument: the decode state stays resident
(device buffers donated in place, the WKV state in VMEM within a window)
instead of round-tripping per token.

``ServeEngine.generate`` is the *lockstep* loop: every request advances
one window at a time, padded to the longest — a workgroup-global barrier
at the serving layer, exactly the group-to-group pattern the paper argues
against.  ``ServeEngine.serve`` replaces it with per-request progress
(point-to-point hand-offs): each slot decodes at its own position, EOS
and per-request budgets are detected *inside* the jitted window, and a
freed slot is re-prefilled with the next queued request without touching
its neighbors' caches.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.ft.watchdog import StepTimeout, StepWatchdog, StragglerDetector
from repro.model import model as M
from repro.model.attention import KVCache, PagedKVCache
from repro.model.recurrent import RecState
from repro.serve import paging

#: Optional dispatch-boundary hook, called as ``hook(phase, kind)`` with
#: phase ``"pre"``/``"post"`` around every fault-plumbed jit dispatch —
#: inside the watchdog worker thread when one is active.  The
#: deterministic interleaving drill (:mod:`repro.serve.interleave`)
#: installs a forced-preemption point here; production leaves it None.
dispatch_hook = None


def make_prefill_step(cfg):
    """(params, tokens, **extras) -> logits (B, S, V).

    ``cfg.prefill_chunks > 1`` splits the request batch into chunks
    processed sequentially (a ``lax.scan``), bounding live activation /
    MoE-dispatch memory — sequences are independent, so this is exact.
    """
    from repro.core.lowering import scan_unroll

    def prefill_step(params, tokens, **kw):
        from repro.model.sharding import _CTX

        n = cfg.prefill_chunks
        b = tokens.shape[0]
        # Mesh-aware: never chunk below one sequence per data shard (chunked
        # batches that don't cover the batch-sharding axes lose parallelism
        # and force replication).
        if _CTX.mesh is not None and _CTX.rules is not None:
            data = _CTX.rules.get("batch")
            size = 1
            if data:
                for a in (data if isinstance(data, tuple) else (data,)):
                    size *= _CTX.mesh.shape[a]
            n = max(1, min(n, b // max(size, 1)))
        if n <= 1 or b % n:
            return M.forward(params, cfg, tokens, **kw)

        def split(x, batch_axis=0):
            return x.reshape(
                x.shape[:batch_axis] + (n, x.shape[batch_axis] // n)
                + x.shape[batch_axis + 1:]
            ).swapaxes(0, batch_axis) if batch_axis else x.reshape(
                (n, b // n) + x.shape[1:]
            )

        tk = split(tokens)
        kw_split = {}
        for key, v in kw.items():
            if key == "positions" and v.ndim == 3 and v.shape[0] == 3:
                kw_split[key] = jnp.moveaxis(
                    v.reshape(3, n, b // n, v.shape[2]), 1, 0
                )
            else:
                kw_split[key] = split(v)

        keys = sorted(kw_split)

        def chunk_fn(_, inputs):
            tok = inputs[0]
            kw_i = dict(zip(keys, inputs[1:]))
            return None, M.forward(params, cfg, tok, **kw_i)

        xs = (tk,) + tuple(kw_split[k] for k in keys)
        _, logits = jax.lax.scan(chunk_fn, None, xs, unroll=scan_unroll())
        return logits.reshape((b,) + logits.shape[2:])

    return prefill_step


# Prompts at/above this length route through the sequence-parallel rules
# by default; below it, sequence sharding costs more in summary hops than
# it saves in per-device work.
SEQ_PREFILL_MIN_T = 1024


def make_seq_prefill_step(cfg, mesh, *, min_len: int = SEQ_PREFILL_MIN_T):
    """Long-context prefill: run the base prefill under ``prefill_seq``
    sharding rules.

    With the sequence mapped to the model axis, recurrent blocks dispatch
    the sequence-parallel WKV path (:mod:`repro.kernels.wkv.seqpar`): each
    device sweeps its own sequence shard with the fused kernel and only
    the O(Dh²) (decay, state) segment summary crosses the ``seq`` axis —
    the prompt tokens are never re-gathered.  Prompts shorter than
    ``min_len`` fall back to the plain prefill rules, where sequence
    sharding would cost more in carry hops than it saves in per-device
    work.
    """
    from repro.model.sharding import make_rules, sharding_context

    base = make_prefill_step(cfg)
    seq_rules = make_rules(mesh, "prefill_seq")
    plain_rules = make_rules(mesh, "prefill")

    def prefill_step(params, tokens, **kw):
        rules = seq_rules if tokens.shape[1] >= min_len else plain_rules
        with mesh, sharding_context(mesh, rules):
            return base(params, tokens, **kw)

    return prefill_step


def make_decode_step(cfg):
    """(params, state, tokens (B,K), length ()) -> (logits, new_state).

    K >= 1: the window width rides straight through ``model.decode_step``
    (K == 1 is classic per-token decode)."""

    def decode_step(params, state, tokens, length, enc_out=None):
        return M.decode_step(params, cfg, state, tokens, length, enc_out=enc_out)

    return decode_step


def make_cache_prefill_step(cfg, mesh=None, *, min_len: int = SEQ_PREFILL_MIN_T,
                            last_only: bool = False, max_len: int | None = None):
    """One-jit prompt prefill *into the decode cache*.

    ``(params, state, tokens (B, P)[, prompt_lengths (B,)]) ->
    (logits (B, P, V), new_state)`` — the whole prompt goes through
    ``model.decode_step`` as a single window starting at position 0, so
    the KV caches and recurrent states fill in one dispatch instead of P
    sequential single-token calls (the WKV part takes the decode-window
    or chunked elevator kernel, not P state round-trips).  ``state`` is
    donated: XLA writes the caches in place.  ``last_only=True`` returns
    logits for the final prompt position only ((B, 1, V)) — what a
    greedy serve loop consumes; the full (B, P, V) projection is for
    scoring callers.

    ``prompt_lengths`` masks ragged prompts: request b's tokens beyond
    ``prompt_lengths[b]`` are padding and contribute *nothing* to any
    state — pad tokens never enter the KV caches or the WKV/RG-LRU
    recurrent states (they used to, silently polluting every request
    shorter than the batch max), each request's cache length ends at its
    own prompt length, and with ``last_only`` the logits are taken at
    each request's final *valid* position.

    ``max_len`` (the position cap the state was built with) is forwarded
    to ``model.decode_step``'s ring-slack trace check.

    With ``mesh``, prompts of at least ``min_len`` tokens run under the
    ``prefill_seq`` sharding rules — the same routing rule as
    :func:`make_seq_prefill_step`, so long prompts compose with the
    sequence-parallel WKV path while the cache still fills in one jit;
    shorter prompts use the plain ``prefill`` rules.
    """
    from repro.model.sharding import make_rules, sharding_context

    def cache_prefill(params, state, tokens, prompt_lengths=None):
        mask = None
        if prompt_lengths is not None:
            p = tokens.shape[1]
            mask = (
                jnp.arange(p, dtype=jnp.int32)[None, :]
                < jnp.asarray(prompt_lengths, jnp.int32)[:, None]
            )
        return M.decode_step(params, cfg, state, tokens, jnp.int32(0),
                             last_only=last_only, token_mask=mask,
                             max_len=max_len)

    if mesh is None:
        return jax.jit(cache_prefill, donate_argnums=(1,))
    # One jit wrapper per rules mode: the sharding context is read at
    # trace time, so a shared cache entry would freeze whichever rules
    # traced first.
    seq_jit = jax.jit(cache_prefill, donate_argnums=(1,))
    short_jit = jax.jit(cache_prefill, donate_argnums=(1,))
    seq_rules = make_rules(mesh, "prefill_seq")
    plain_rules = make_rules(mesh, "prefill")

    def prefill(params, state, tokens, prompt_lengths=None):
        fn, rules = (
            (seq_jit, seq_rules) if tokens.shape[1] >= min_len
            else (short_jit, plain_rules)
        )
        with mesh, sharding_context(mesh, rules):
            return fn(params, state, tokens, prompt_lengths)

    return prefill


def audit_jit_entrypoints(cfg, *, batch: int = 2, max_len: int = 64,
                          decode_window: int = 4, prompt: int = 32):
    """Registration hook for :mod:`repro.analysis.donation`: every jit the
    serve engine dispatches, with abstract arguments sufficient to lower
    it (nothing executes — params and state are ShapeDtypeStructs).

    Adding a jit to the engine means adding it here; the donation pass
    audits exactly this list, so an unregistered jit is a review-visible
    gap rather than a silently un-audited one.
    """
    from repro.analysis.donation import JitEntry

    sds = jax.ShapeDtypeStruct
    eng = ServeEngine(cfg, params=M.abstract_params(cfg), max_len=max_len,
                      decode_window=decode_window)
    k = max(1, decode_window)
    p = _bucket32(prompt)
    params = eng.params
    state = M.abstract_decode_state(
        cfg, batch=batch, max_len=max_len,
        insert_window=max(k, _bucket32(prompt)),
    )
    i32, b = jnp.int32, batch
    vec = sds((b,), i32)
    key = sds((2,), jnp.uint32)
    rows = cfg.admit_rows if 0 < cfg.admit_rows < b else None
    here = "src/repro/serve/engine.py:ServeEngine"
    return [
        JitEntry(
            "serve.decode_step", eng._decode,
            (params, state, sds((b, 1), i32), sds((), i32)),
            f"{here}.__post_init__", donor="_decode",
        ),
        JitEntry(
            "serve.prefill", eng._prefill,
            (params, state, sds((b, p), i32), vec),
            "src/repro/serve/engine.py:make_cache_prefill_step",
            donor="make_cache_prefill_step",
        ),
        JitEntry(
            "serve.window", eng._window_step(k, last=False),
            (params, state, sds((b, 1), i32), vec),
            f"{here}._window_step", donor="_window_step",
        ),
        JitEntry(
            "serve.serve_window", eng._serve_window(k, 0.0, 0, None),
            (params, state, sds((b, 1), i32), vec, vec, vec,
             sds((b,), jnp.bool_), vec, key),
            f"{here}._serve_window", donor="_serve_window",
        ),
        JitEntry(
            "serve.admit", eng._admit_step(p, 0.0, 0, None, rows),
            (params, state, sds((rows or b, p), i32), sds((b,), jnp.bool_),
             vec, vec, vec, vec, vec, vec, sds((b,), jnp.bool_),
             sds((b, 1), i32), key) + ((sds((rows,), i32),) if rows else ()),
            f"{here}._admit_step", donor="_admit_step",
        ),
        JitEntry(
            "serve.shadow_checksum", eng._shadow_csum, (state,),
            f"{here}.__post_init__", donated=None, donate_argnums=None,
        ),
    ] + _paged_jit_entrypoints(cfg, batch=batch, max_len=max_len,
                               decode_window=decode_window, prompt=prompt)


def _paged_jit_entrypoints(cfg, *, batch, max_len, decode_window, prompt):
    """Paged-engine jits for the donation audit: the decode window lowered
    against a pooled state (donation must alias the pools in place), and
    the paged admit with its page-table / prefix-entry operands."""
    from repro.analysis.donation import JitEntry

    sds = jax.ShapeDtypeStruct
    eng = ServeEngine(cfg, params=M.abstract_params(cfg), max_len=max_len,
                      decode_window=decode_window, paged=True)
    k = max(1, decode_window)
    p = _bucket32(prompt)
    iw = max(k, p)
    params = eng.params
    state = M.abstract_decode_state(
        cfg, batch=batch, max_len=max_len, insert_window=iw,
        paged=M.PageSpec(page_size=eng.page_size),
    )
    ctl = paging.PagedController(cfg, state, batch=batch, max_len=max_len)
    entry = M.abstract_decode_state(cfg, batch=1, max_len=max_len,
                                    insert_window=iw)
    rec, kv = paging.split_entry(entry)
    ring = [kv[i] for i, role in enumerate(ctl.roles) if role == "copy"]
    tables = [sds((batch, g.nl), jnp.int32) for g in ctl.geoms]
    i32, b = jnp.int32, batch
    vec = sds((b,), i32)
    bvec = sds((b,), jnp.bool_)
    key = sds((2,), jnp.uint32)
    here = "src/repro/serve/engine.py:ServeEngine"
    return [
        JitEntry(
            "serve.paged_window", eng._serve_window(k, 0.0, 0, None),
            (params, state, sds((b, 1), i32), vec, vec, vec, bvec, vec,
             key),
            f"{here}._serve_window", donor="_serve_window",
        ),
        JitEntry(
            "serve.paged_admit",
            eng._admit_step_paged(p, 0.0, 0, None, ctl.roles),
            (params, state, sds((b, p), i32), bvec, vec, vec, bvec,
             tables, tables, rec, ring, vec, vec, vec, vec, vec, bvec,
             sds((b, 1), i32), key),
            f"{here}._admit_step_paged", donor="_admit_step_paged",
        ),
    ]


@dataclasses.dataclass
class Request:
    """One serve request: a prompt, a generation budget, and an optional
    wall-clock deadline (milliseconds from serve start; ``None`` falls
    back to the serve-level default, which may itself be ``None`` = no
    deadline)."""

    tokens: Any                    # (P,) int prompt token ids
    max_new_tokens: int = 16
    deadline_ms: float | None = None
    #: Paged engines only: id from :meth:`ServeEngine.register_prefix`.
    #: The prompt must extend the registered prefix; its page-aligned head
    #: is admitted by sharing/copying the prefix entry instead of being
    #: re-prefilled.
    prefix_id: int | None = None


#: Terminal per-request outcomes (see :class:`RequestResult`):
#:   ok        — completed by exhausting its token budget
#:   eos       — completed by sampling ``eos_id``
#:   deadline  — killed at its wall-clock deadline (tokens are partial)
#:   shed      — rejected at admission: the bounded queue was full
#:   dropped   — chaos/client drop mid-flight (tokens are partial)
#:   recovered — completed (budget or EOS) after >= 1 quarantine+re-prefill
#:   corrupt   — checksum-detected silent corruption recurred past the
#:               recovery cap (tokens are the last verified prefix)
OUTCOMES = ("ok", "eos", "deadline", "shed", "dropped", "recovered",
            "corrupt")

#: ``last_serve_stats`` keys, in the (fixed) order they are packed into
#: the snapshot stats vector — append only, never reorder.
SERVE_STAT_KEYS = (
    "decode_dispatches", "admissions", "slot_steps", "quarantines",
    "recoveries", "dispatch_retries", "dispatch_drops",
    "watchdog_timeouts", "stragglers", "deadline_hits", "shed",
    "req_drops", "snapshots", "page_waits", "prefix_admissions",
    "corruptions", "checksum_spot_checks",
)

#: A request whose checksum-detected corruption recurs past this many
#: recovery attempts ends with the terminal ``corrupt`` outcome instead
#: of cycling forever (a persistently corrupting slot is a hardware
#: problem, not a retry problem).
MAX_CORRUPTION_RECOVERIES = 3


@dataclasses.dataclass
class RequestResult:
    """One served request's tokens plus its typed outcome.

    Array-like (``__array__`` / ``len`` / indexing / ``.size`` /
    ``.tolist``) so result lists drop into code written against the bare
    token-array contract; ``outcome`` and ``recoveries`` carry the
    fault-isolation story (how the request ended, and how many
    quarantine+re-prefill cycles it survived on the way).
    """

    tokens: np.ndarray
    outcome: str = "ok"
    recoveries: int = 0

    def __array__(self, dtype=None, copy=None):
        a = self.tokens if dtype is None else self.tokens.astype(dtype)
        return a.copy() if copy else a

    def __len__(self):
        return int(self.tokens.size)

    def __iter__(self):
        return iter(self.tokens)

    def __getitem__(self, i):
        return self.tokens[i]

    @property
    def size(self) -> int:
        return int(self.tokens.size)

    def tolist(self):
        return self.tokens.tolist()


def _bucket32(length: int) -> int:
    """Prompt-length bucket (next multiple of 32): one shared rounding for
    admission jit-cache keys and local-ring ``insert_window`` sizing, so
    the two can't silently diverge."""
    return -(-max(int(length), 1) // 32) * 32


#: Per-slot state checksum (:func:`repro.model.model.decode_state_checksum`)
#: under the trace scope that the state checks' device time is read by.
_state_checksum = jax.named_scope("serve.state_check")(
    M.decode_state_checksum)


@jax.named_scope("serve.reset_rows")
def _reset_slot_rows(state, rows: jax.Array):
    """Zero the decode state of the slots marked in ``rows`` (B,) bool —
    and only those: neighbors' caches are untouched (a ``jnp.where`` per
    leaf along the batch axis, no reallocation, donation-friendly).

    Per-request cache lengths and recurrent states reset to zero; *finite*
    KV cache contents are left in place — with length 0 no stale slot is
    reachable (the positional masks in ``_decode_attention`` only admit
    slots whose absolute position is below the slot's own query
    positions, and those get overwritten by the new prompt's insert).
    Non-finite KV entries in the reset rows are scrubbed to zero: a
    masked slot contributes ``weight 0 × value``, which is exactly 0 for
    finite stale values but NaN for a poisoned row — masking hides stale
    data, it does not disarm NaNs, so quarantine recovery must scrub
    them (a no-op rewrite for healthy rows, bit-identical fault-free).
    """

    def fix(node):
        if isinstance(node, KVCache):
            extra = node.k.ndim - 4              # stacked (L, B, ...) or not
            m = rows.reshape((1,) * extra + (-1,))
            mk = rows.reshape((1,) * extra + (-1, 1, 1, 1))

            def scrub(a):
                return jnp.where(
                    mk & ~jnp.isfinite(a), jnp.zeros((), a.dtype), a
                )

            return KVCache(
                k=scrub(node.k), v=scrub(node.v),
                length=jnp.where(m, 0, node.length),
            )
        if isinstance(node, RecState):
            extra = node.conv.ndim - 3

            def zero(leaf):
                m = rows.reshape(
                    (1,) * extra + (-1,) + (1,) * (leaf.ndim - extra - 1)
                )
                return jnp.where(m, jnp.zeros((), leaf.dtype), leaf)

            return RecState(h=zero(node.h), conv=zero(node.conv))
        if isinstance(node, PagedKVCache):
            # Paged rendering of the reset: unmap the rows (and zero their
            # lengths) — pool *contents* stay put, since pages are shared
            # storage.  Stale finite data is unreachable (length 0 +
            # positional masks) and non-finite garbage is scrubbed when a
            # page is next mapped (:func:`repro.serve.paging._admit_kv_one`).
            extra = node.page_table.ndim - 2
            m = rows.reshape((1,) * extra + (-1,))
            return PagedKVCache(
                k=node.k, v=node.v,
                page_table=jnp.where(m[..., None], -1, node.page_table),
                length=jnp.where(m, 0, node.length),
                s_view=node.s_view, page_size=node.page_size,
            )
        raise TypeError(type(node))

    return jax.tree.map(
        fix, state,
        is_leaf=lambda x: isinstance(x, (KVCache, PagedKVCache, RecState)),
    )


def _batch_axis(node) -> int:
    """Slot axis of a dense decode-state node (stacked ``(L, B, ...)`` or
    not)."""
    if isinstance(node, KVCache):
        return node.k.ndim - 4
    if isinstance(node, RecState):
        return node.conv.ndim - 3
    raise TypeError(type(node))


def _is_dense_node(x) -> bool:
    return isinstance(x, (KVCache, RecState))


def _state_rows(state, rows: jax.Array):
    """The slots ``rows`` (R,) int32 of a dense decode state, in that
    order: a state of R slots."""

    def take(node):
        ax = _batch_axis(node)
        if isinstance(node, KVCache):
            length = jnp.broadcast_to(node.length, node.k.shape[:ax + 1])
            return KVCache(k=jnp.take(node.k, rows, axis=ax),
                           v=jnp.take(node.v, rows, axis=ax),
                           length=jnp.take(length, rows, axis=ax))
        return RecState(h=jnp.take(node.h, rows, axis=ax),
                        conv=jnp.take(node.conv, rows, axis=ax))

    return jax.tree.map(take, state, is_leaf=_is_dense_node)


def _put_state_rows(state, part, rows: jax.Array):
    """``state`` with its slots ``rows`` (R,) int32, distinct, replaced by
    ``part`` (a state of R slots, as :func:`_state_rows` gives); every
    other slot's bits are untouched."""

    def put(node, new):
        at = (slice(None),) * _batch_axis(node) + (rows,)
        if isinstance(node, KVCache):
            length = jnp.broadcast_to(node.length, node.k.shape[:len(at)])
            return KVCache(k=node.k.at[at].set(new.k),
                           v=node.v.at[at].set(new.v),
                           length=length.at[at].set(new.length))
        return RecState(h=node.h.at[at].set(new.h),
                        conv=node.conv.at[at].set(new.conv))

    return jax.tree.map(put, state, part, is_leaf=_is_dense_node)


@jax.named_scope("serve.sample")
def _sample_tokens(logits, base_key, req_ids, tok_idx, temperature, top_k):
    """Sample one token per slot from ``logits`` (B, V).

    ``temperature <= 0`` is greedy argmax.  Otherwise temperature/top-k
    categorical with a per-slot PRNG key derived as
    ``fold_in(fold_in(base_key, req_ids[b]), tok_idx[b])`` — a pure
    function of (request id, token index), so a request's sampled stream
    is invariant to the decode window K, to which slot it landed in, and
    to what its batch neighbors are doing.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits.astype(jnp.float32) / float(temperature)
    if top_k and top_k < lg.shape[-1]:
        kth = jax.lax.top_k(lg, int(top_k))[0][..., -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)

    def one(rid, n, row):
        key = jax.random.fold_in(jax.random.fold_in(base_key, rid), n)
        return jax.random.categorical(key, row)

    return jax.vmap(one)(req_ids, tok_idx, lg).astype(jnp.int32)


@dataclasses.dataclass
class ServeEngine:
    """Batched greedy server: one-jit prompt prefill into the cache, then a
    scan-based decode loop over K-token windows with donated state.

    ``decode_window`` (K) is the number of tokens generated per decode
    dispatch: each dispatch is one jitted function whose body is a
    ``lax.scan`` over K single-token ``model.decode_step`` calls, with the
    decode state donated at the jit boundary — XLA aliases the KV caches
    and the (B, H, Dh, Dh) WKV states in place instead of copying them per
    step, and the per-dispatch Python/runtime overhead amortizes ~K×.
    ``generate`` issues exactly ``ceil(num_new_tokens / K)`` decode
    dispatches.

    ``mesh`` routes long prompts through the sequence-parallel prefill
    rules (see :func:`make_cache_prefill_step`); decode windows run under
    the ``decode`` rules.

    ``serve(requests)`` is the continuous-batching scheduler on top of the
    same jitted pieces: per-request lengths, in-window sampling and EOS
    detection, and slot recycling (see :meth:`serve`).
    """

    cfg: Any
    params: Any
    max_len: int = 256
    decode_window: int = 8
    mesh: Any = None
    #: Paged KV storage: ``serve()`` replaces per-slot dense caches with
    #: page pools + per-slot page tables (see :mod:`repro.serve.paging`).
    #: ``page_size`` must be a multiple of the 32-token admit bucket;
    #: ``pool_pages`` caps allocatable private pages per KV node pool
    #: (``None`` = dense-equivalent capacity, which can never starve).
    paged: bool = False
    page_size: int = 32
    pool_pages: int | None = None

    def __post_init__(self):
        cfg = self.cfg
        if self.paged:
            if self.page_size < 32 or self.page_size % 32:
                raise ValueError(
                    f"page_size must be a positive multiple of the 32-token "
                    f"admit bucket, got {self.page_size}"
                )
            if self.mesh is not None:
                raise NotImplementedError(
                    "paged serving does not compose with a mesh yet"
                )
        # Per-token fallback step (the decode_window=1 shape).  state is
        # donated here too: without it every step copies the full cache
        # pytree through HBM just to update one slot.
        self._decode = jax.jit(
            lambda p, s, t, l: M.decode_step(p, cfg, s, t, l),
            donate_argnums=(1,),
        )
        # last_only: generate() consumes only the final prompt position's
        # logits — don't materialize the (B, P, V) tensor at prefill.
        self._prefill = make_cache_prefill_step(
            cfg, self.mesh, last_only=True, max_len=self.max_len
        )
        # Shadow checksum: the host-side spot check recomputes the state
        # checksum out-of-band and compares it to the last emitted one.
        # Read-only by construction — donating here would consume the
        # live decode state the serve loop still owns.
        self._shadow_csum = jax.jit(M.decode_state_checksum)
        self._windows = {}
        self._admits = {}
        self._admits_paged = {}
        self._serve_windows = {}
        # Prefix registry: id -> prompt tokens; entries cache the one-time
        # batch-1 prefill of a prefix's page-aligned head per insert
        # window (its recurrent states + dense KV views).
        self._prefixes: dict[int, np.ndarray] = {}
        self._prefix_entries: dict = {}
        self._null_entries: dict = {}
        self._next_prefix_id = 0
        # Observability: decode dispatches issued by the last generate().
        self.last_decode_dispatches = 0
        # serve() counters: decode dispatches / admission prefills /
        # total slot-steps scanned (incl. masked dead-slot steps).
        self.last_serve_stats: dict[str, int] = {}
        # Paged byte accounting from the last paged serve(): pool vs
        # dense-equivalent bytes, peak mapped bytes, audit violations.
        self.last_paged_stats: dict[str, int] = {}

    def _window_step(self, k: int, last: bool):
        """Jitted K-token decode window, cached per (k, last).

        Emits the k tokens fed through the model and carries (state, next
        token, position).  The final window of a generation run stops one
        decode short — the last emitted token needs no successor — so it
        scans k-1 steps and appends the carried token.
        """
        fn = self._windows.get((k, last))
        if fn is None:
            cfg = self.cfg
            steps = k - 1 if last else k

            def win(p, state, cur, pos):
                def body(carry, _):
                    st, tok, ps = carry
                    logits, st = M.decode_step(p, cfg, st, tok, ps)
                    nxt = jnp.argmax(logits[:, -1], axis=-1)
                    nxt = nxt.astype(jnp.int32)[:, None]
                    return (st, nxt, ps + 1), tok

                (state, cur, pos), toks = jax.lax.scan(
                    body, (state, cur, pos), None, length=steps
                )
                toks = jnp.moveaxis(toks[..., 0], 0, 1)      # (B, steps)
                if last:
                    toks = jnp.concatenate([toks, cur], axis=1)
                return toks, state, cur, pos

            fn = self._under_mesh(jax.jit(win, donate_argnums=(1,)), "decode")
            self._windows[(k, last)] = fn
        return fn

    # ------------------------------------------------------------------
    # Continuous batching: admission + masked decode windows
    # ------------------------------------------------------------------

    def _admit_step(self, p: int, temperature: float, top_k: int,
                    eos_id: int | None, rows: int | None = None):
        """Jitted slot admission, cached per (prompt bucket, sampling cfg,
        rows).

        Re-prefills the admitted slots' prompts into the shared decode
        state without touching neighbors: admitted rows are zeroed
        (:func:`_reset_slot_rows`), then one masked ``decode_step`` call
        runs the whole (B, P) batch with a token mask that is all-False
        outside the admitted rows — so every other slot's KV cache,
        recurrent state, and length are bit-identical afterwards.  Also
        samples each admitted slot's next token at its ``tok_idx``:
        0 for a fresh request (its first token), n for a quarantine
        recovery whose "prompt" is the original prompt plus the n
        already-accepted tokens — the ``fold_in(req_id, token_idx)``
        sampling keys then guarantee the resumed stream is the one the
        fault interrupted.

        With ``rows`` (the model's ``admit_rows``, below the slot count)
        the prefill runs on ``rows`` slots only: a last operand ``sel``
        (rows,) int32 of distinct slots, the admitted ones among them,
        picks them out of the state, ``tokens`` is (rows, P) in ``sel``'s
        order, and the prefilled rows are written back in place.  The
        prefill then costs rows / B of the all-slots one; the slots
        outside ``sel`` are not read.

        With an engine ``mesh`` the admission prefill runs under the same
        sharding rules :func:`make_cache_prefill_step` would pick for a
        prompt of this bucket (``prefill_seq`` at/above
        :data:`SEQ_PREFILL_MIN_T`, plain ``prefill`` below).
        """
        key = (p, temperature, top_k, eos_id, rows)
        fn = self._admits.get(key)
        if fn is None:
            cfg, max_len = self.cfg, self.max_len

            def prefill(params, state, tokens, admit_row, plen, tok_idx,
                        req_ids, base_key):
                state = _reset_slot_rows(state, admit_row)
                mask = admit_row[:, None] & (
                    jnp.arange(p, dtype=jnp.int32)[None, :] < plen[:, None]
                )
                logits, state = M.decode_step(
                    params, cfg, state, tokens, jnp.int32(0),
                    token_mask=mask, last_only=True, max_len=max_len,
                )
                tok0 = _sample_tokens(
                    logits[:, -1], base_key, req_ids, tok_idx,
                    temperature, top_k,
                )
                return state, tok0

            def admit(params, state, tokens, admit_row, plen, tok_idx,
                      lengths, counts, budgets, req_ids, active, cur,
                      base_key, *sel):
                # Entry checksum: the state as handed to this dispatch,
                # *before* any mutation — the host chains it against the
                # previous dispatch's exit checksum to catch silent
                # corruption of non-admitted rows between dispatches.
                csum_in = _state_checksum(state)
                if rows is None:
                    state, tok0 = prefill(params, state, tokens, admit_row,
                                          plen, tok_idx, req_ids, base_key)
                else:
                    (sel,) = sel
                    part, tok_sel = prefill(
                        params, _state_rows(state, sel), tokens,
                        admit_row[sel], plen[sel], tok_idx[sel],
                        req_ids[sel], base_key)
                    state = _put_state_rows(state, part, sel)
                    tok0 = jnp.zeros(admit_row.shape,
                                     tok_sel.dtype).at[sel].set(tok_sel)
                lengths = jnp.where(admit_row, plen, lengths)
                counts = jnp.where(admit_row, tok_idx + 1, counts)
                done = counts >= budgets
                if eos_id is not None:
                    done |= tok0 == eos_id
                active = jnp.where(admit_row, ~done, active)
                cur = jnp.where(admit_row[:, None], tok0[:, None], cur)
                csum_out = _state_checksum(state)
                return (state, lengths, counts, active, cur, tok0,
                        csum_in, csum_out)

            fn = self._under_mesh(
                jax.jit(admit, donate_argnums=(1,)),
                "prefill_seq" if p >= SEQ_PREFILL_MIN_T else "prefill",
            )
            self._admits[key] = fn
        return fn

    def _under_mesh(self, fn, mode: str):
        """``fn`` traced and run under the engine mesh's ``mode`` sharding
        rules; ``fn`` itself without a mesh.

        The rules are read at trace time, so every jit that runs on the
        mesh needs them: on a TPU they are what places the Pallas kernels
        inside a ``shard_map`` (the compiler cannot partition a kernel).
        """
        if self.mesh is None:
            return fn
        from repro.model.sharding import make_rules, sharding_context

        mesh, rules = self.mesh, make_rules(self.mesh, mode)

        def run(*args):
            with mesh, sharding_context(mesh, rules):
                return fn(*args)

        return run

    # -- paged admission + prefix sharing --------------------------------

    def register_prefix(self, tokens) -> int:
        """Register a shared prompt prefix (paged engines only).

        Returns an id for :attr:`Request.prefix_id`.  The prefix's
        page-aligned head (``floor(len / page_size) × page_size`` tokens)
        is prefilled once per serve; every request carrying the id is
        admitted by *sharing* the resulting KV pages (full-view nodes) /
        *copying* the ring content and recurrent states (wrapping local
        nodes, WKV S / RG-LRU h) instead of re-prefilling those tokens.
        Prompts must strictly extend the prefix.
        """
        if not self.paged:
            raise ValueError("prefix sharing requires a paged engine")
        toks = np.asarray(tokens, np.int32).reshape(-1)
        if toks.size < self.page_size:
            raise ValueError(
                f"prefix of {toks.size} tokens is shorter than one "
                f"{self.page_size}-token page — nothing to share"
            )
        if toks.size >= self.max_len:
            raise ValueError(
                f"prefix of {toks.size} tokens leaves no room to decode "
                f"within max_len={self.max_len}"
            )
        pid = self._next_prefix_id
        self._next_prefix_id += 1
        self._prefixes[pid] = toks
        return pid

    def _prefix_entry(self, pid: int, insert_window: int):
        """(start_len, rec nodes, per-KV-node dense (k, v) views) for a
        registered prefix: one batch-1 dense prefill of its page-aligned
        head, cached per (prefix, insert window) — the shared state every
        prefix admission copies from (``insert_window`` is the serve's,
        which always covers the prefix: prompts extend it, and the window
        is bucketed from the longest prompt)."""
        key = (pid, insert_window)
        ent = self._prefix_entries.get(key)
        if ent is None:
            toks = self._prefixes[pid]
            start = (toks.size // self.page_size) * self.page_size
            st = M.init_decode_state(
                self.cfg, batch=1, max_len=self.max_len,
                insert_window=insert_window,
            )
            _, st = self._prefill(
                self.params, st, jnp.asarray(toks[:start])[None, :], None)
            rec, kv = paging.split_entry(st)
            ent = (start, rec, kv)
            self._prefix_entries[key] = ent
        return ent

    def _null_entry(self, insert_window: int):
        """Zero-filled prefix-entry operands (rec nodes + dense KV views)
        for admissions that carry no prefix — every use inside the jit is
        gated on ``prefix_rows``, so the zeros are never observable."""
        ent = self._null_entries.get(insert_window)
        if ent is None:
            st = M.init_decode_state(
                self.cfg, batch=1, max_len=self.max_len,
                insert_window=insert_window,
            )
            ent = paging.split_entry(st)
            self._null_entries[insert_window] = ent
        return ent

    def _admit_step_paged(self, p: int, temperature: float, top_k: int,
                          eos_id: int | None, roles: tuple):
        """Paged slot admission, cached per (suffix bucket, sampling cfg,
        KV-node roles).  The dense :meth:`_admit_step` plus the page-table
        surgery of :func:`repro.serve.paging.apply_admission`: admitted
        rows are unmapped and re-pointed at freshly reserved pages (which
        are scrubbed of non-finite garbage on the way in), prefix rows
        start from the shared entry's recurrent state / ring content at
        ``start_len``, and only the prompt *suffix* beyond ``start_len``
        is prefilled — the cost win the pool exists for.
        """
        key = (p, temperature, top_k, eos_id, roles)
        fn = self._admits_paged.get(key)
        if fn is None:
            cfg, max_len = self.cfg, self.max_len

            def admit(params, state, tokens, admit_row, plen, start_len,
                      prefix_rows, tables, scrubs, rec_entries,
                      ring_contents, tok_idx, lengths, counts, budgets,
                      req_ids, active, cur, base_key):
                csum_in = _state_checksum(state)
                state = _reset_slot_rows(state, admit_row)
                state = paging.apply_admission(
                    state, roles, admit_row, prefix_rows, start_len,
                    tables, scrubs, rec_entries, ring_contents,
                )
                mask = admit_row[:, None] & (
                    jnp.arange(p, dtype=jnp.int32)[None, :] < plen[:, None]
                )
                logits, state = M.decode_step(
                    params, cfg, state, tokens, start_len,
                    token_mask=mask, last_only=True, max_len=max_len,
                )
                tok0 = _sample_tokens(
                    logits[:, -1], base_key, req_ids, tok_idx,
                    temperature, top_k,
                )
                lengths = jnp.where(admit_row, start_len + plen, lengths)
                counts = jnp.where(admit_row, tok_idx + 1, counts)
                done = counts >= budgets
                if eos_id is not None:
                    done |= tok0 == eos_id
                active = jnp.where(admit_row, ~done, active)
                cur = jnp.where(admit_row[:, None], tok0[:, None], cur)
                csum_out = _state_checksum(state)
                return (state, lengths, counts, active, cur, tok0,
                        csum_in, csum_out)

            fn = jax.jit(admit, donate_argnums=(1,))
            self._admits_paged[key] = fn
        return fn

    def _serve_window(self, k: int, temperature: float, top_k: int,
                      eos_id: int | None):
        """Jitted continuous decode window, cached per (k, sampling cfg).

        A ``lax.scan`` of k single-token ``decode_step`` calls with
        per-slot lengths.  Each step: finished/empty slots are masked out
        of the model (``token_mask`` freezes their caches and recurrent
        states via ``jnp.where`` — bit-identical across the window), the
        next token is sampled in-window (temperature / top-k with the
        per-request PRNG key), and EOS / budget exhaustion flips the
        slot's ``active`` bit *inside the jit* — the host only sees the
        window-level result.

        Fault detection rides the same scan at zero extra dispatches: a
        per-slot finiteness flag (``isfinite`` reduced over the recurrent
        states — :func:`repro.model.model.decode_state_finite` — plus the
        slot's own logits row, which covers NaN KV rows the moment they
        are attended) *quarantines* a poisoned slot inside the jit: its
        ``active`` bit flips off, so the very next step's ``token_mask``
        freezes its state via the existing dead-slot machinery, its
        garbage token is never emitted, and — because every per-slot
        update is a ``jnp.where`` along batch — its neighbors' streams
        stay bit-identical.  The quarantine mask (B,) comes back to the
        host, which re-prefills the victim from its accepted prefix.

        Silent-corruption detection rides the same dispatch: the window
        emits a per-slot state checksum at *entry* (the state exactly as
        received) and at *exit*
        (:func:`repro.model.model.decode_state_checksum` — integer
        wraparound sums of the raw state bits, so the comparison is exact
        and reduction-order-free).  The host chains exit(n) == entry(n+1):
        anything that flips state bits between dispatches — a finite-but-
        wrong bit flip the ``isfinite`` quarantine can never see — breaks
        the chain at the very next window.

        Emits (tokens (k, B), emit-mask (k, B), quarantined (B,),
        entry/exit checksums (B,) uint32).
        """
        key = (k, temperature, top_k, eos_id)
        fn = self._serve_windows.get(key)
        if fn is None:
            cfg, max_len = self.cfg, self.max_len

            def win(params, state, cur, lengths, counts, budgets, active,
                    req_ids, base_key):
                csum_in = _state_checksum(state)
                quar0 = jnp.zeros_like(active)

                def body(carry, _):
                    state, cur, lengths, counts, active, quar = carry
                    logits, state = M.decode_step(
                        params, cfg, state, cur, lengths,
                        token_mask=active[:, None], last_only=True,
                        max_len=max_len,
                    )
                    lg = logits[:, -1]
                    with jax.named_scope("serve.state_check"):
                        finite = M.decode_state_finite(state) & jnp.all(
                            jnp.isfinite(lg.astype(jnp.float32)), axis=-1
                        )
                    bad = active & ~finite
                    quar = quar | bad
                    active = active & ~bad
                    nxt = _sample_tokens(
                        lg, base_key, req_ids, counts, temperature, top_k,
                    )
                    emit = active
                    lengths = lengths + emit.astype(jnp.int32)
                    counts = counts + emit.astype(jnp.int32)
                    done = counts >= budgets
                    if eos_id is not None:
                        done |= nxt == eos_id
                    active = active & ~done
                    cur = jnp.where(emit[:, None], nxt[:, None], cur)
                    return (
                        (state, cur, lengths, counts, active, quar),
                        (nxt, emit),
                    )

                (state, cur, lengths, counts, active, quar), (toks, emits) = (
                    jax.lax.scan(
                        body,
                        (state, cur, lengths, counts, active, quar0), None,
                        length=k,
                    )
                )
                csum_out = _state_checksum(state)
                return (state, cur, lengths, counts, active, quar, toks,
                        emits, csum_in, csum_out)

            fn = self._under_mesh(jax.jit(win, donate_argnums=(1,)), "decode")
            self._serve_windows[key] = fn
        return fn

    def _dispatch(self, kind, fn, args, *, chaos, watchdog, straggler,
                  stats, max_retries, backoff_s, index):
        """One dispatch through the fault plumbing: chaos injection runs
        first, inside the watchdog thread, *before* the jitted ``fn``
        consumes its donated arguments — which is exactly what makes the
        retry safe: an injected drop raises pre-consumption, and an
        injected hang aborts cooperatively at the watchdog's generation
        fence without ever touching the buffers.  (A *real* device hang
        that dies inside the jit leaves donated buffers unusable; that is
        the snapshot/restore path's job, not the retry's.)  Retries back
        off exponentially from ``backoff_s``.
        """

        def call():
            hook = dispatch_hook
            if hook is not None:
                hook("pre", kind)
            if chaos is not None:
                chaos.before_dispatch(
                    kind, index,
                    cancelled=(watchdog.cancelled if watchdog is not None
                               else None),
                )
            out = fn(*args)
            if hook is not None:
                hook("post", kind)
            return out

        attempt = 0
        while True:
            try:
                t0 = time.monotonic()
                # hostsafety: ok(retry re-passes args only pre-consumption)
                # A retried dispatch passes the donated args tuple again —
                # legal because every retried failure (chaos drop, hang at
                # the watchdog fence) raises *before* fn consumes the
                # buffers; post-consumption faults go to snapshot/restore,
                # never back through this loop.
                out = watchdog.run(call) if watchdog is not None else call()
                if straggler is not None and kind == "window":
                    if straggler.observe(time.monotonic() - t0):
                        stats["stragglers"] += 1
                return out
            except StepTimeout:
                stats["watchdog_timeouts"] += 1
            except Exception as e:  # noqa: BLE001 — filtered below
                from repro.serve.chaos import DispatchDropped

                if not isinstance(e, DispatchDropped):
                    raise
                stats["dispatch_drops"] += 1
            attempt += 1
            stats["dispatch_retries"] += 1
            if attempt > max_retries:
                raise RuntimeError(
                    f"{kind} dispatch failed after {max_retries} retries"
                )
            time.sleep(backoff_s * (2 ** (attempt - 1)))

    def serve(self, requests, *, slots: int = 4, temperature: float = 0.0,
              top_k: int = 0, eos_id: int | None = None, seed: int = 0,
              deadline_ms: float | None = None,
              max_queue: int | None = None,
              watchdog_timeout_s: float | None = None,
              max_dispatch_retries: int = 3,
              retry_backoff_s: float = 0.02,
              snapshot_every: int = 0,
              snapshot_dir: str | None = None,
              restore_from: str | None = None,
              chaos: Any = None,
              recoverable: bool | None = None,
              checksum_every: int = 0):
        """Continuous-batching scheduler: decode ``requests`` through a
        fixed pool of ``slots`` batch slots with per-request progress —
        and with the blast radius of any failure confined to one slot.

        Each request (a :class:`Request`, or anything with ``tokens`` /
        ``max_new_tokens``) is admitted into a free slot (a single masked
        prefill that cannot touch neighbors' caches), decodes at its own
        position, and frees its slot the moment it hits ``eos_id`` or its
        own ``max_new_tokens`` — detected inside the jitted window, so a
        finished request never burns another dispatch waiting for the
        slowest batch member (the lockstep barrier :meth:`generate`
        pays).  Freed slots are recycled to the next queued request in
        arrival order.

        Fault isolation (the paper's point-to-point argument as a
        robustness property — a fault delays one slot's hand-off, never
        a batch-global barrier):

        * a slot whose state or logits go non-finite is **quarantined**
          inside the jitted window (see :meth:`_serve_window`) and
          **recovered** by re-admitting the request from its accepted
          prefix (prompt + tokens emitted so far) — the read-side dual of
          :func:`_reset_slot_rows`; with the per-(request, token-index)
          sampling keys the resumed stream is exactly the one the fault
          interrupted, and every other slot is bit-identical to a
          fault-free run;
        * ``deadline_ms`` (serve-wide default; ``Request.deadline_ms``
          overrides) kills requests past their wall-clock budget with a
          typed ``deadline`` outcome instead of letting them squat slots;
        * ``max_queue`` bounds the admission backlog: beyond ``slots``
          immediately-admissible requests, at most ``max_queue`` may
          wait; later arrivals are **shed** (typed outcome, no tokens)
          instead of queueing unboundedly;
        * failed / hung dispatches are retried with exponential backoff
          (``max_dispatch_retries``, ``retry_backoff_s``), a hang being
          detected by a per-dispatch
          :class:`~repro.ft.watchdog.StepWatchdog` when
          ``watchdog_timeout_s`` is set (straggler dispatches are
          EWMA-flagged in ``last_serve_stats['stragglers']``);
        * ``snapshot_every`` > 0 checkpoints the whole engine — slot
          table, queues, per-request progress, device state — to
          ``snapshot_dir`` every N decode dispatches
          (:mod:`repro.checkpoint.checkpoint`); ``restore_from`` resumes
          a preempted serve bit-identically (same requests/args/seed);
        * ``checksum_every`` > 0 arms silent-corruption detection: every
          dispatch emits per-slot entry/exit state checksums which the
          host chains (exit(n) must equal entry(n+1) — a finite-but-
          wrong bit flip breaks the chain at the next window even though
          ``isfinite`` never fires), plus a shadow recompute spot check
          every M windows; a mismatched slot is quarantined, its
          unverified window tokens are rolled back, and the request is
          re-admitted from its last verified prefix (outcome
          ``recovered``; ``corrupt`` once corruption recurs past
          :data:`MAX_CORRUPTION_RECOVERIES`).

        ``chaos`` accepts a :class:`repro.serve.chaos.ChaosInjector` to
        drill all of the above deterministically.  ``recoverable`` sizes
        the local-attention rings for worst-case recovery re-prefills
        (prompt + whole budget); it defaults on when chaos / snapshots /
        restore are in play and off otherwise, where the ring sizing —
        and hence fault-free streams — match the pre-fault-isolation
        engine exactly.

        Sampling: greedy at ``temperature`` 0 (the parity-testable mode),
        else temperature / top-k categorical, keyed per (request, token
        index) — a request's stream is reproducible under a fixed
        ``seed`` regardless of ``decode_window``, slot assignment, batch
        composition, or how many faults it survived.

        Returns a list of :class:`RequestResult` (array-like: the
        generated tokens, prompt not included) with typed ``outcome``
        (ok / eos / deadline / shed / dropped / recovered) and the
        per-request recovery count.  Stats land in ``last_serve_stats``.
        """
        session = ServeSession(
            self, requests, slots=slots, temperature=temperature,
            top_k=top_k, eos_id=eos_id, seed=seed, deadline_ms=deadline_ms,
            max_queue=max_queue, watchdog_timeout_s=watchdog_timeout_s,
            max_dispatch_retries=max_dispatch_retries,
            retry_backoff_s=retry_backoff_s, snapshot_every=snapshot_every,
            snapshot_dir=snapshot_dir, restore_from=restore_from,
            chaos=chaos, recoverable=recoverable,
            checksum_every=checksum_every)
        try:
            while session.busy:
                session.step()
        finally:
            session.close()
        return session.results()

    # -- engine snapshot / restore ---------------------------------------

    def _serve_meta(self, b, k_w, insert_window, n, seed, ctl):
        """Snapshot compatibility vector: geometry + paging config.  A
        restore must be a bit-identical re-run, so everything that shapes
        the jits or the page pools is pinned here."""
        return np.asarray(
            [b, k_w, insert_window, n, seed, int(ctl is not None),
             self.page_size,
             -1 if self.pool_pages is None else int(self.pool_pages),
             0 if ctl is None else ctl.shared_total],
            np.int64)

    def _snapshot_serve(self, snapshot_dir, stats, state, cur, lengths,
                        counts, budgets, req_ids, active, slot_req, pending,
                        recover_q, outputs, outcomes, recoveries,
                        b, k_w, insert_window, n, seed, ctl=None,
                        corruptions=None, saver=None):
        """Checkpoint the whole serve loop as ONE atomic tree: device
        state + slot table + queues + per-request progress + stats.

        Everything — including the ragged per-request outputs (flattened
        to ``out_flat`` + ``out_off`` offsets) — goes through one
        :func:`checkpoint.save`, so a crash mid-snapshot can never leave
        device state and bookkeeping describing different moments.  The
        RNG needs no saving: sampling keys are ``fold_in(req_id,
        token_idx)`` off ``PRNGKey(seed)``, both of which the restore
        re-derives, which is exactly what makes resumed streams
        bit-identical.
        """
        from repro.checkpoint import checkpoint as C

        out_off = np.zeros(n + 1, np.int64)
        for i, o in enumerate(outputs):
            out_off[i + 1] = out_off[i] + len(o)
        out_flat = np.asarray(
            [t for o in outputs for t in o], np.int32)
        codes = np.asarray(
            [-1 if oc is None else OUTCOMES.index(oc) for oc in outcomes],
            np.int32)
        host = {
            "slot_req": np.asarray(slot_req, np.int32),
            "pending": np.asarray(list(pending), np.int32),
            "recover_q": np.asarray(list(recover_q), np.int32),
            "out_flat": out_flat,
            "out_off": out_off,
            "outcome_codes": codes,
            "recoveries": np.asarray(recoveries, np.int64),
            "corruptions": np.asarray(
                corruptions if corruptions is not None else [0] * n,
                np.int64),
            "stats": np.asarray(
                [stats[k] for k in SERVE_STAT_KEYS], np.int64),
        }
        if ctl is not None:
            # Page-pool bookkeeping rides the same atomic tree: owner
            # arrays (page -> slot / FREE / SHARED) and the high-water
            # mark — the device page tables themselves are in ``state``.
            for key, val in ctl.snapshot_tree().items():
                host["pg_" + key] = val
        tree = {
            "device": {
                "state": state, "cur": cur, "lengths": lengths,
                "counts": counts, "budgets": budgets, "req_ids": req_ids,
                "active": active,
            },
            "host": host,
            "meta": self._serve_meta(b, k_w, insert_window, n, seed, ctl),
        }
        if saver is not None:
            # Fleet replicas snapshot through an AsyncSaver: the host copy
            # is taken synchronously (so the tree is still one atomic
            # moment) and the write overlaps the next windows.  A failed
            # background write surfaces here on the next snapshot.
            saver.save_async(snapshot_dir, stats["decode_dispatches"], tree)
        else:
            C.save(snapshot_dir, stats["decode_dispatches"], tree)

    def _restore_serve(self, restore_from, b, k_w, insert_window, n, seed,
                       state_template, ctl=None):
        """Resume a snapshotted serve.  The caller must pass the same
        requests / slots / decode_window / seed the snapshot was taken
        under (validated against the snapshot's meta); device arrays come
        back through :func:`checkpoint.restore` against a fresh template
        (restore is template-driven, so the host-side extras in the same
        file are simply not materialized on device), ragged host arrays
        are read straight from the snapshot's ``arrays.npz``.
        """
        from pathlib import Path

        from repro.checkpoint import checkpoint as C

        step = C.latest_step(restore_from)
        if step is None:
            raise FileNotFoundError(f"no serve snapshot under {restore_from}")
        template = {
            "device": {
                "state": state_template,
                "cur": jnp.zeros((b, 1), jnp.int32),
                "lengths": jnp.zeros((b,), jnp.int32),
                "counts": jnp.zeros((b,), jnp.int32),
                "budgets": jnp.zeros((b,), jnp.int32),
                "req_ids": jnp.zeros((b,), jnp.int32),
                "active": jnp.zeros((b,), bool),
            },
        }
        with np.load(Path(restore_from) / f"step_{step}"
                     / "arrays.npz") as data:
            meta = data["meta"]
            host = {k.split("/", 1)[1]: data[k] for k in data.files
                    if k.startswith("host/")}
        want = self._serve_meta(b, k_w, insert_window, n, seed, ctl)
        if not np.array_equal(meta, want):
            raise ValueError(
                f"snapshot meta {meta.tolist()} does not match this serve "
                f"call {want.tolist()} — restore needs the same requests, "
                "slots, decode_window, seed, and paging config"
            )
        if ctl is not None:
            ctl.restore({k[3:]: v for k, v in host.items()
                         if k.startswith("pg_")})
        tree, _ = C.restore(restore_from, template, step=step)
        d = tree["device"]
        outputs = [
            [int(t) for t in host["out_flat"]
             [host["out_off"][i]: host["out_off"][i + 1]]]
            for i in range(n)
        ]
        outcomes = [
            None if c < 0 else OUTCOMES[c]
            for c in host["outcome_codes"]
        ]
        stats = {k: int(v)
                 for k, v in zip(SERVE_STAT_KEYS, host["stats"])}
        corruptions = host.get("corruptions", np.zeros(n, np.int64))
        return (d["state"], d["cur"], d["lengths"], d["counts"],
                d["budgets"], d["req_ids"], d["active"],
                [int(s) for s in host["slot_req"]],
                collections.deque(int(i) for i in host["pending"]),
                collections.deque(int(i) for i in host["recover_q"]),
                outputs, outcomes,
                [int(r) for r in host["recoveries"]],
                [int(c) for c in corruptions], stats)

    def generate(self, prompts: jax.Array, num_new_tokens: int,
                 prompt_lengths=None) -> jax.Array:
        """prompts: (B, P) int32 -> (B, P + num_new_tokens).

        ``prompt_lengths`` (B,) marks ragged prompts: tokens at/beyond a
        request's length are padding — masked out of every cache and
        recurrent state at prefill — and generation continues from each
        request's own final position (the output keeps the dense layout:
        row b's generated tokens start at column P regardless of its
        prompt length).  Decoding itself stays lockstep; :meth:`serve` is
        the continuous scheduler.
        """
        b, p_len = prompts.shape
        k_w = max(1, int(self.decode_window))
        # insert_window sizes the local-attention ring slack for the widest
        # window any decode_step call inserts (the whole prompt at
        # prefill).  Bucketed to a multiple of 32 so the decode-state
        # shapes — and with them the cached window jits — don't recompile
        # for every distinct prompt length (extra slack is harmless: the
        # ring is capped at max_len either way).
        state = M.init_decode_state(
            self.cfg, batch=b, max_len=self.max_len,
            insert_window=max(k_w, _bucket32(p_len)),
        )
        logits, state = self._prefill(self.params, state, prompts,
                                      prompt_lengths)
        self.last_decode_dispatches = 0
        if num_new_tokens <= 0:
            return prompts
        out = [prompts]
        cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        pos = (
            jnp.int32(p_len) if prompt_lengths is None
            else jnp.asarray(prompt_lengths, jnp.int32)
        )
        left = num_new_tokens
        while left > 0:
            k = min(k_w, left)
            fn = self._window_step(k, last=(k == left))
            toks, state, cur, pos = fn(self.params, state, cur, pos)
            self.last_decode_dispatches += 1
            out.append(toks)
            left -= k
        return jnp.concatenate(out, axis=1)

class ServeSession:
    """One resumable continuous-batching serve loop — the engine-side half
    of a fleet replica.

    :meth:`ServeEngine.serve` is this object driven to completion.  A
    :class:`repro.serve.fleet.FleetRouter` instead constructs one session
    per replica engine and *interleaves* :meth:`step` calls across them:
    each ``step()`` is exactly one scheduler iteration (deadline sweep,
    admission, one decode window), so N sessions in one process make
    independent progress the same way lane 2's fake devices simulate a
    mesh.

    ``external=True`` starts the local queue empty: the session still
    sees the FULL request list — slot shapes, request ids, the
    insert-window bucket and the snapshot meta are then identical on
    every replica, which is the precondition for bit-identical streams
    under rescheduling and for snapshot handoff — but requests only
    enter via :meth:`enqueue` (router assignment) or
    :meth:`enqueue_handoff` (resume from a dead replica's snapshot).

    ``clock_origin`` anchors deadline arithmetic: a router passes one
    shared origin so ``deadline_ms`` counts the time a request spent
    waiting in the shared fleet queue, not just post-assignment decode
    time.  ``saver`` (a :class:`repro.checkpoint.checkpoint.AsyncSaver`)
    moves snapshot writes off the dispatch path.
    """

    def __init__(self, engine, requests, *, slots: int = 4,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_id: int | None = None, seed: int = 0,
                 deadline_ms: float | None = None,
                 max_queue: int | None = None,
                 watchdog_timeout_s: float | None = None,
                 max_dispatch_retries: int = 3,
                 retry_backoff_s: float = 0.02,
                 snapshot_every: int = 0,
                 snapshot_dir: str | None = None,
                 restore_from: str | None = None,
                 chaos: Any = None,
                 recoverable: bool | None = None,
                 checksum_every: int = 0,
                 clock=time.monotonic,
                 clock_origin: float | None = None,
                 external: bool = False,
                 saver: Any = None):
        eng = engine
        self.eng = eng
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id
        self.seed = seed
        self.deadline_ms = deadline_ms
        self.max_retries = max_dispatch_retries
        self.backoff_s = retry_backoff_s
        self.snapshot_every = snapshot_every
        self.snapshot_dir = snapshot_dir
        self.chaos = chaos
        self.checksum_every = int(checksum_every)
        self.saver = saver
        self.external = external
        self._clock = clock
        self.closed = False
        #: Scheduler iterations run (the ``serve.step`` span's number).
        self.steps = 0

        reqs = [
            r if hasattr(r, "tokens") else Request(tokens=r)
            for r in requests
        ]
        self.reqs = reqs
        n = len(reqs)
        self.n = n
        b = max(1, min(int(slots), n)) if n else 1
        self.b = b
        k_w = max(1, int(eng.decode_window))
        self.k_w = k_w
        self.prompts_np = [np.asarray(r.tokens, np.int32).reshape(-1)
                           for r in reqs]
        self.p_lens = [int(a.size) for a in self.prompts_np]
        self.outputs: list[list[int]] = [[] for _ in range(n)]
        self.outcomes: list[str | None] = [None] * n
        self.recoveries = [0] * n
        self.corruptions = [0] * n
        self.stats = {k: 0 for k in SERVE_STAT_KEYS}
        #: Requests that reached a terminal outcome in THIS session, in
        #: completion order — a fleet router drains these after every
        #: step, so results delivered before a replica dies are never
        #: re-run.
        self.newly_done: collections.deque[int] = collections.deque()
        ps = int(eng.page_size)
        self.pid_of: list[int | None] = [None] * n
        self.start_of = [0] * n
        for i, (r, pl) in enumerate(zip(reqs, self.p_lens)):
            if pl < 1:
                raise ValueError("request prompt must be non-empty")
            if int(r.max_new_tokens) < 1:
                raise ValueError("max_new_tokens must be >= 1")
            pid = getattr(r, "prefix_id", None)
            if pid is not None:
                if not eng.paged:
                    raise ValueError(
                        "Request.prefix_id requires a paged engine")
                pre = eng._prefixes.get(pid)
                if pre is None:
                    raise ValueError(f"unknown prefix id {pid}")
                if (pl < pre.size
                        or not np.array_equal(self.prompts_np[i][:pre.size],
                                              pre)):
                    raise ValueError(
                        f"request {i}: prompt does not extend registered "
                        f"prefix {pid}")
                start = (pre.size // ps) * ps
                if pl > start:
                    self.pid_of[i], self.start_of[i] = pid, start
                # else the prompt IS the page-aligned prefix: the entry
                # leaves no suffix token to prefill from — admit cold.
            if pl + int(r.max_new_tokens) > eng.max_len:
                # A request that cannot fit the engine's position limit is
                # load to refuse, not a caller bug that should abort every
                # other request in the batch: typed shed outcome.
                self.outcomes[i] = "shed"
                self.stats["shed"] += 1
                self.newly_done.append(i)
        live = [i for i in range(n) if self.outcomes[i] is None]
        if recoverable is None:
            recoverable = (chaos is not None or restore_from is not None
                           or snapshot_every > 0 or external)
        # Recovery re-prefills replay prompt + accepted tokens in one
        # window: size the local-attention ring slack for the worst case
        # (a request quarantined on its last token) when recovery is in
        # play.  Off the recovery paths, keep the original sizing — ring
        # shapes feed attention reductions, so changing them for free
        # would perturb fault-free bit-parity with older baselines.
        worst = max(
            (self.p_lens[i] + int(reqs[i].max_new_tokens) if recoverable
             else self.p_lens[i])
            for i in live
        ) if live else 1
        insert_window = max(k_w, _bucket32(worst))
        self.insert_window = insert_window
        #: Slots one dense admission prefills (the model's
        #: ``admit_rows``); ``None``: all ``b``.
        r = int(eng.cfg.admit_rows)
        self.admit_rows = r if 0 < r < b and not eng.paged else None
        ctl = None
        if eng.paged:
            # One shared-page region per registered prefix in use this
            # serve: prefill each prefix's aligned head once (cached),
            # reserve its pages in every full-view pool, and upload the
            # K/V content before any admission.
            used_pids = sorted({self.pid_of[i] for i in live
                                if self.pid_of[i] is not None})
            shared_map, entries, nxt = {}, {}, 1
            for pid in used_pids:
                start, rec, kv = eng._prefix_entry(pid, insert_window)
                shared_map[pid] = (nxt, start // ps)
                nxt += start // ps
                entries[pid] = (rec, kv)
            spec = M.PageSpec(page_size=ps, private_pages=eng.pool_pages,
                              shared_pages=nxt - 1)
            state = M.init_decode_state(
                eng.cfg, batch=b, max_len=eng.max_len,
                insert_window=insert_window, paged=spec,
            )
            ctl = paging.PagedController(
                eng.cfg, state, batch=b, max_len=eng.max_len,
                shared_map=shared_map,
            )
            if entries:
                state = paging.upload_shared(state, ctl, entries)
            for i in live:
                if not ctl.fits_capacity(
                        self.p_lens[i] + int(reqs[i].max_new_tokens),
                        self.start_of[i]):
                    # Needs more private pages than the pool ever has:
                    # waiting can never help — shed, don't deadlock.
                    self.outcomes[i] = "shed"
                    self.stats["shed"] += 1
                    self.newly_done.append(i)
        else:
            state = M.init_decode_state(
                eng.cfg, batch=b, max_len=eng.max_len,
                insert_window=insert_window,
            )
        self.ctl = ctl
        self.state = state
        self.lengths = jnp.zeros((b,), jnp.int32)
        self.counts = jnp.zeros((b,), jnp.int32)
        self.budgets = jnp.zeros((b,), jnp.int32)
        self.req_ids = jnp.zeros((b,), jnp.int32)
        self.active = jnp.zeros((b,), bool)
        self.cur = jnp.zeros((b, 1), jnp.int32)
        self.base_key = jax.random.PRNGKey(seed)

        if external:
            self.pending: collections.deque[int] = collections.deque()
        else:
            self.pending = collections.deque(
                i for i in range(n) if self.outcomes[i] is None)
        self.recover_q: collections.deque[int] = collections.deque()
        self.slot_req = [-1] * b
        self.active_np = np.zeros(b, bool)

        self.watchdog = (StepWatchdog(watchdog_timeout_s)
                         if watchdog_timeout_s is not None else None)
        self.straggler = StragglerDetector(warmup=1)
        self.t_origin = clock_origin if clock_origin is not None else clock()
        self.any_deadline = (
            deadline_ms is not None
            or any(getattr(r, "deadline_ms", None) is not None
                   for r in reqs))

        # Checksum chain state: after any dispatch, _csum_base holds the
        # per-slot exit checksums the next dispatch's entry must match.
        self._csum_base = np.zeros(b, np.uint32)
        self._csum_have = False
        self._since_spot = 0

        if restore_from is not None:
            (self.state, self.cur, self.lengths, self.counts, self.budgets,
             self.req_ids, self.active, self.slot_req, self.pending,
             self.recover_q, self.outputs, self.outcomes, self.recoveries,
             self.corruptions, self.stats) = eng._restore_serve(
                restore_from, b, k_w, insert_window, n, seed, state, ctl)
            self.active_np = np.array(self.active)
        elif max_queue is not None and not external:
            # Bounded admission queue: b requests admit immediately, at
            # most max_queue wait; shed the later arrivals (typed
            # outcome), never queue unboundedly.
            cap = b + max(0, int(max_queue))
            while len(self.pending) > cap:
                ri = self.pending.pop()
                self.outcomes[ri] = "shed"
                self.stats["shed"] += 1
                self.newly_done.append(ri)

    # -- queue interface (router-facing) --------------------------------

    @property
    def busy(self) -> bool:
        """True while this session has local work (queued or in-flight)."""
        return bool(self.pending or self.recover_q or self.active_np.any())

    def enqueue(self, ri: int):
        """Assign request ``ri`` (an index into the full request list) to
        this session's local queue."""
        if self.outcomes[ri] is not None:
            raise ValueError(f"request {ri} already terminal "
                             f"({self.outcomes[ri]})")
        self.pending.append(ri)

    def enqueue_handoff(self, ri: int, accepted) -> None:
        """Resume request ``ri`` from another replica's snapshot: seed its
        output with the ``accepted`` token prefix and queue it through the
        recovery path (re-prefill of prompt + accepted tokens).  The
        per-(request, token-index) sampling keys make the continuation
        bit-identical to the stream the dead replica was producing."""
        self.outputs[ri] = [int(t) for t in accepted]
        self.outcomes[ri] = None
        self.recoveries[ri] += 1
        self.stats["recoveries"] += 1
        self.recover_q.append(ri)

    def queue_depth(self) -> int:
        """Queued + in-flight request count (router load signal)."""
        return (len(self.pending) + len(self.recover_q)
                + int(self.active_np.sum()))

    def recovery_debt_steps(self, window: int = 1) -> int:
        """Modeled decode steps this session must spend on re-prefills
        before its recovery queue is clean (router placement bias — see
        :func:`repro.core.cost_model.serve_recovery_steps`)."""
        from repro.core import cost_model

        total = 0
        for ri in self.recover_q:
            isolated, _ = cost_model.serve_recovery_steps(
                [self.p_lens[ri]], [len(self.outputs[ri])], 0,
                window=window)
            total += isolated
        return total

    def drain_done(self) -> list[int]:
        """Pop and return requests that reached a terminal outcome since
        the last drain."""
        out = []
        while self.newly_done:
            out.append(self.newly_done.popleft())
        return out

    # -- outcome helpers -------------------------------------------------

    def _req_deadline(self, ri):
        d = getattr(self.reqs[ri], "deadline_ms", None)
        return self.deadline_ms if d is None else d

    def _resolve(self, ri):
        if self.recoveries[ri] > 0:
            self.outcomes[ri] = "recovered"
        elif (self.eos_id is not None and self.outputs[ri]
                and self.outputs[ri][-1] == self.eos_id):
            self.outcomes[ri] = "eos"
        else:
            self.outcomes[ri] = "ok"
        self.newly_done.append(ri)

    def _free_slot(self, slot):
        self.slot_req[slot] = -1
        if self.ctl is not None:
            self.ctl.free_slot(slot)

    # -- the scheduler iteration ----------------------------------------

    def step(self):
        """One scheduler iteration: deadline sweep, admission (recoveries
        first), one decode window with quarantine / checksum / chaos
        bookkeeping.  Exactly the loop body :meth:`ServeEngine.serve`
        always ran — extracted so a fleet can interleave replicas.

        Under a profiler the iteration is a ``serve.step`` span holding
        ``serve.admit`` and ``serve.window``, each split into
        ``.prepare`` (host arrays, jit lookup), ``.dispatch``, ``.sync``
        (device reads) and ``.bookkeep``; with no profiler running the
        spans record nothing."""
        with StepTraceAnnotation("serve.step", step_num=self.steps):
            self._sweep_deadlines()
            self._admit()
            self._decode_window()
        self.steps += 1

    def _sweep_deadlines(self):
        if not self.any_deadline:
            return
        now_ms = (self._clock() - self.t_origin) * 1e3
        killed = False
        for slot in np.nonzero(self.active_np)[0]:
            ri = self.slot_req[slot]
            dl = self._req_deadline(ri)
            if dl is not None and now_ms > dl:
                self.outcomes[ri] = "deadline"
                self.stats["deadline_hits"] += 1
                self.newly_done.append(ri)
                self.active_np[slot] = False
                self._free_slot(slot)
                killed = True
        if killed:
            self.active = jnp.asarray(self.active_np)
        for q in (self.recover_q, self.pending):
            for _ in range(len(q)):
                ri = q.popleft()
                dl = self._req_deadline(ri)
                if dl is not None and now_ms > dl:
                    self.outcomes[ri] = "deadline"
                    self.stats["deadline_hits"] += 1
                    self.newly_done.append(ri)
                else:
                    q.append(ri)

    def _admit(self):
        eng, b = self.eng, self.b
        ctl = self.ctl
        free = [i for i in range(b) if not self.active_np[i]]
        take: list[int] = []
        slot_alloc: dict[int, tuple] = {}
        group_pid: int | None = None
        cap = len(free)
        if self.admit_rows is not None:
            cap = min(cap, self.admit_rows)
        while len(take) < cap and (self.recover_q or self.pending):
            q = self.recover_q if self.recover_q else self.pending
            ri = q[0]
            if ctl is not None:
                pid = self.pid_of[ri]
                if pid is not None:
                    if group_pid is None:
                        group_pid = pid
                    elif pid != group_pid:
                        # One prefix entry per admission dispatch: a
                        # second prefix waits for the next round.
                        break
                alloc = ctl.try_admit(
                    free[len(take)],
                    self.p_lens[ri] + int(self.reqs[ri].max_new_tokens),
                    pid, self.start_of[ri])
                if alloc is None:
                    # Pool pressure: the head-of-line request waits for
                    # pages freed by completions — it is never skipped
                    # (no starvation reorder).
                    self.stats["page_waits"] += 1
                    break
                slot_alloc[free[len(take)]] = alloc
            q.popleft()
            take.append(ri)
        if not take:
            return
        with TraceAnnotation("serve.admit") as span:
            with TraceAnnotation("serve.admit.prepare"):
                # A recovery's "prompt" is the original prompt plus its
                # accepted tokens; fresh requests have none.
                used = free[: len(take)]
                admit_np = np.zeros(b, bool)
                plen_np = np.zeros(b, np.int32)
                tokidx_np = np.zeros(b, np.int32)
                bud_np = np.array(self.budgets)
                rid_np = np.array(self.req_ids)
                full = {
                    ri: np.concatenate([
                        self.prompts_np[ri],
                        np.asarray(self.outputs[ri], np.int32),
                    ])
                    for ri in take
                }
                if ctl is None:
                    p_b = _bucket32(max(full[ri].size for ri in take))
                    tok_np = np.zeros((b, p_b), np.int32)
                    for slot, ri in zip(used, take):
                        t_arr = full[ri]
                        tok_np[slot, : t_arr.size] = t_arr
                        admit_np[slot] = True
                        plen_np[slot] = t_arr.size
                        tokidx_np[slot] = len(self.outputs[ri])
                        bud_np[slot] = int(self.reqs[ri].max_new_tokens)
                        rid_np[slot] = ri
                        self.slot_req[slot] = ri
                    self.budgets = jnp.asarray(bud_np)
                    self.req_ids = jnp.asarray(rid_np)
                    r = self.admit_rows
                    sel = ()
                    if r is not None:
                        # The admitted slots, then idle ones, then busy
                        # ones (masked: their bits pass through).
                        rest = sorted(
                            set(range(b)) - set(used),
                            key=lambda slot: bool(self.active_np[slot]))
                        picked = np.array(used + rest[: r - len(used)],
                                          np.int32)
                        tok_np = tok_np[picked]
                        sel = (jnp.asarray(picked),)
                    fn = eng._admit_step(
                        p_b, self.temperature, self.top_k, self.eos_id, r)
                    args = (eng.params, self.state, jnp.asarray(tok_np),
                            jnp.asarray(admit_np), jnp.asarray(plen_np),
                            jnp.asarray(tokidx_np), self.lengths,
                            self.counts, self.budgets, self.req_ids,
                            self.active, self.cur, self.base_key) + sel
                else:
                    # Paged: only the suffix past each request's shared-
                    # prefix start is prefilled; the prefix rides in as
                    # copied state / shared pages.
                    p_b = _bucket32(max(
                        full[ri].size - self.start_of[ri] for ri in take))
                    tok_np = np.zeros((b, p_b), np.int32)
                    start_np = np.zeros(b, np.int32)
                    prefix_np = np.zeros(b, bool)
                    for slot, ri in zip(used, take):
                        t_arr = full[ri][self.start_of[ri]:]
                        tok_np[slot, : t_arr.size] = t_arr
                        admit_np[slot] = True
                        plen_np[slot] = t_arr.size
                        start_np[slot] = self.start_of[ri]
                        prefix_np[slot] = self.start_of[ri] > 0
                        tokidx_np[slot] = len(self.outputs[ri])
                        bud_np[slot] = int(self.reqs[ri].max_new_tokens)
                        rid_np[slot] = ri
                        self.slot_req[slot] = ri
                        if self.start_of[ri] > 0:
                            self.stats["prefix_admissions"] += 1
                    self.budgets = jnp.asarray(bud_np)
                    self.req_ids = jnp.asarray(rid_np)
                    tables, scrubs = [], []
                    for i_node, g in enumerate(ctl.geoms):
                        t_rows = np.full((b, g.nl), -1, np.int32)
                        s_rows = np.full((b, g.nl), -1, np.int32)
                        for slot in used:
                            t_rows[slot] = slot_alloc[slot][0][i_node]
                            s_rows[slot] = slot_alloc[slot][1][i_node]
                        tables.append(jnp.asarray(t_rows))
                        scrubs.append(jnp.asarray(s_rows))
                    if group_pid is not None:
                        _, rec, kv = eng._prefix_entry(
                            group_pid, self.insert_window)
                    else:
                        rec, kv = eng._null_entry(self.insert_window)
                    ring = [kv[i] for i, role in enumerate(ctl.roles)
                            if role == "copy"]
                    fn = eng._admit_step_paged(
                        p_b, self.temperature, self.top_k, self.eos_id,
                        ctl.roles)
                    args = (eng.params, self.state, jnp.asarray(tok_np),
                            jnp.asarray(admit_np), jnp.asarray(plen_np),
                            jnp.asarray(start_np), jnp.asarray(prefix_np),
                            tables, scrubs, rec, ring,
                            jnp.asarray(tokidx_np), self.lengths,
                            self.counts, self.budgets, self.req_ids,
                            self.active, self.cur, self.base_key)
            span.set_metadata(rows=len(take), slots=b, bucket=p_b,
                              prompt_tokens=int(plen_np.sum()))
            with TraceAnnotation("serve.admit.dispatch"):
                (self.state, self.lengths, self.counts, self.active,
                 self.cur, tok0, entry_csum, exit_csum) = eng._dispatch(
                    "admit", fn, args,
                    chaos=self.chaos, watchdog=self.watchdog,
                    straggler=self.straggler, stats=self.stats,
                    max_retries=self.max_retries, backoff_s=self.backoff_s,
                    index=self.stats["decode_dispatches"],
                )
            with TraceAnnotation("serve.admit.sync"):
                tok0_np = np.asarray(tok0)
                self.active_np = np.array(self.active)
                if self.checksum_every > 0:
                    entry_np = np.asarray(entry_csum)
                    exit_np = np.asarray(exit_csum)
            with TraceAnnotation("serve.admit.bookkeep"):
                if self.checksum_every > 0:
                    # Chain check for the rows this admission did NOT
                    # touch: their state is frozen through the jit, so a
                    # mismatch means the bits changed between dispatches.
                    self._chain_check(entry_np, skip=admit_np, emits_np=None)
                    self._csum_base = exit_np.copy()
                    self._csum_have = True
                for slot, ri in zip(used, take):
                    self.outputs[ri].append(int(tok0_np[slot]))
                    if not self.active_np[slot]:
                        # Done at admission (budget 1 / instant EOS).
                        self._resolve(ri)
                        self._free_slot(slot)
                self.stats["admissions"] += 1

    def _decode_window(self):
        if not self.active_np.any():
            return
        eng = self.eng
        with TraceAnnotation("serve.window",
                             active=int(self.active_np.sum()), k=self.k_w):
            with TraceAnnotation("serve.window.prepare"):
                if self.chaos is not None:
                    self.state, _ = self.chaos.maybe_poison(
                        self.state, self.active_np,
                        self.stats["decode_dispatches"], self.slot_req)
                    self.state, _ = self.chaos.maybe_bitflip(
                        self.state, self.active_np,
                        self.stats["decode_dispatches"], self.slot_req)
                fn = eng._serve_window(self.k_w, self.temperature,
                                       self.top_k, self.eos_id)
            with TraceAnnotation("serve.window.dispatch"):
                (self.state, self.cur, self.lengths, self.counts,
                 self.active, quar, toks, emits, entry_csum,
                 exit_csum) = eng._dispatch(
                    "window", fn,
                    (eng.params, self.state, self.cur, self.lengths,
                     self.counts, self.budgets, self.active, self.req_ids,
                     self.base_key),
                    chaos=self.chaos, watchdog=self.watchdog,
                    straggler=self.straggler, stats=self.stats,
                    max_retries=self.max_retries, backoff_s=self.backoff_s,
                    index=self.stats["decode_dispatches"],
                )
            with TraceAnnotation("serve.window.sync"):
                toks_np = np.asarray(toks)
                emits_np = np.asarray(emits)
                active_np = np.array(self.active)
                quar_np = np.asarray(quar)
                if self.checksum_every > 0:
                    entry_np = np.asarray(entry_csum)
                    exit_np = np.asarray(exit_csum)
            with TraceAnnotation("serve.window.bookkeep"):
                for step_i in range(self.k_w):
                    for slot in np.nonzero(emits_np[step_i])[0]:
                        self.outputs[self.slot_req[slot]].append(
                            int(toks_np[step_i, slot]))
                prev_active, self.active_np = self.active_np, active_np
                self.stats["decode_dispatches"] += 1
                self.stats["slot_steps"] += self.k_w * self.b
                corrupt_np = np.zeros(self.b, bool)
                if self.checksum_every > 0:
                    # Checksum chain: this window's entry checksum must
                    # equal the last dispatch's exit checksum.  In-jit
                    # quarantined slots are skipped here — the NaN path
                    # already recovers them.
                    corrupt_np = self._chain_check(
                        entry_np, skip=quar_np, emits_np=emits_np)
                    self._csum_base = exit_np.copy()
                    self._csum_have = True
                # Quarantined slots: queue the victim for re-prefill
                # recovery from its accepted prefix.
                for slot in np.nonzero(quar_np)[0]:
                    ri = self.slot_req[slot]
                    self.stats["quarantines"] += 1
                    self.stats["recoveries"] += 1
                    self.recoveries[ri] += 1
                    self.recover_q.append(ri)
                    self._free_slot(slot)
                # Completions: active before, inactive after, not
                # quarantined and not checksum-corrupt (a corrupt slot's
                # "completion" was computed from bad bits — it re-queues
                # instead).
                for slot in np.nonzero(prev_active & ~self.active_np
                                       & ~quar_np & ~corrupt_np)[0]:
                    ri = self.slot_req[slot]
                    if ri >= 0:
                        self._resolve(ri)
                        self._free_slot(slot)
                if self.chaos is not None:
                    slot = self.chaos.maybe_drop_request(
                        self.active_np, self.stats["decode_dispatches"],
                        self.slot_req)
                    if slot is not None:
                        ri = self.slot_req[slot]
                        self.outcomes[ri] = "dropped"
                        self.stats["req_drops"] += 1
                        self.newly_done.append(ri)
                        self.active_np[slot] = False
                        self._free_slot(slot)
                        self.active = jnp.asarray(self.active_np)
                if self.checksum_every > 0:
                    self._since_spot += 1
                    if self._since_spot >= self.checksum_every:
                        self._spot_check()
                if (self.snapshot_every > 0 and self.snapshot_dir is not None
                        and self.stats["decode_dispatches"]
                        % self.snapshot_every == 0):
                    self.snapshot_now()
                if self.chaos is not None:
                    self.chaos.check_preempt(self.stats["decode_dispatches"])
                    self.chaos.check_replica_kill(
                        self.stats["decode_dispatches"])

    # -- silent-corruption detection -------------------------------------

    def _chain_check(self, entry_np, *, skip, emits_np):
        """Compare a dispatch's entry checksums against the previous
        dispatch's exit checksums.  Slots in ``skip`` (admitted rows,
        in-jit quarantined rows) are excluded.  For window dispatches,
        ``emits_np`` lets the detector roll back the tokens the corrupted
        window emitted — they were computed from bad bits, and the
        re-admission regenerates them from the last verified prefix.
        Returns the (B,) bool mask of corrupt slots."""
        corrupt = np.zeros(self.b, bool)
        if not self._csum_have:
            return corrupt
        for slot in range(self.b):
            if skip[slot] or self.slot_req[slot] < 0:
                continue
            if entry_np[slot] == self._csum_base[slot]:
                continue
            corrupt[slot] = True
            rollback = (int(emits_np[:, slot].sum())
                        if emits_np is not None else 0)
            self._corrupted(slot, rollback)
        if corrupt.any():
            self.active = jnp.asarray(self.active_np)
        return corrupt

    def _corrupted(self, slot: int, rollback: int):
        ri = self.slot_req[slot]
        self.stats["corruptions"] += 1
        self.stats["quarantines"] += 1
        self.corruptions[ri] += 1
        if rollback:
            del self.outputs[ri][len(self.outputs[ri]) - rollback:]
        self.active_np[slot] = False
        self._free_slot(slot)
        if self.corruptions[ri] > MAX_CORRUPTION_RECOVERIES:
            # Persistent corruption is a hardware problem, not a retry
            # problem: terminal typed outcome, last verified prefix kept.
            self.outcomes[ri] = "corrupt"
            self.newly_done.append(ri)
        else:
            self.stats["recoveries"] += 1
            self.recoveries[ri] += 1
            self.recover_q.append(ri)

    def _spot_check(self):
        """Shadow recompute: re-checksum the live state out-of-band and
        compare against the last emitted exit checksums.  The chain
        catches anything that flips bits *between* dispatches; this
        catches corruption after the most recent emission (and would
        catch an emission path that lies)."""
        self._since_spot = 0
        if not self._csum_have:
            return
        self.stats["checksum_spot_checks"] += 1
        shadow = np.asarray(self.eng._shadow_csum(self.state))
        bad = False
        for slot in range(self.b):
            if self.slot_req[slot] < 0:
                continue
            if shadow[slot] != self._csum_base[slot]:
                self._corrupted(slot, rollback=0)
                bad = True
        if bad:
            self.active = jnp.asarray(self.active_np)
        self._csum_base = shadow.copy()

    # -- snapshot / teardown ---------------------------------------------

    def snapshot_now(self):
        self.eng._snapshot_serve(
            self.snapshot_dir, self.stats, self.state, self.cur,
            self.lengths, self.counts, self.budgets, self.req_ids,
            self.active, self.slot_req, self.pending, self.recover_q,
            self.outputs, self.outcomes, self.recoveries,
            self.b, self.k_w, self.insert_window, self.n, self.seed,
            self.ctl, corruptions=self.corruptions, saver=self.saver)
        self.stats["snapshots"] += 1

    def close(self):
        """Finalize stats and run the paged audit.  Idempotent; runs in
        ``finally`` position so preemption/kill exceptions still leave
        ``last_serve_stats`` and the audit behind."""
        if self.closed:
            return
        self.closed = True
        if self.saver is not None:
            self.saver.wait()
        self.eng.last_serve_stats = self.stats
        if self.ctl is not None:
            self.ctl.audit(self.state, self.active_np, self.slot_req)
            self.eng.last_paged_stats = {
                "page_size": int(self.eng.page_size),
                "shared_pages": self.ctl.shared_total,
                "pool_bytes": self.ctl.pool_bytes(),
                "dense_bytes": self.ctl.dense_bytes(),
                "peak_mapped_bytes": self.ctl.peak_mapped_bytes,
                "page_table_violations": len(self.ctl.violations),
            }

    def results(self) -> list[RequestResult]:
        out = []
        for i in range(self.n):
            if self.outcomes[i] is None:   # defensive: loop exit ⇒ terminal
                self._resolve(i)
            out.append(RequestResult(
                tokens=np.asarray(self.outputs[i], np.int32),
                outcome=self.outcomes[i], recoveries=self.recoveries[i],
            ))
        return out
