"""Teacher-forced replay through the serving program's model calls.

The engine's admit program calls ``model.decode_step`` once over a
masked batch of prompts, and its window program calls it over the
tokens that follow.  :func:`teacher_forced_logits` makes the same calls
with the tokens given rather than sampled, so the logits it returns can
be held against a reference's full forward pass over the same sequences
(the served path through the decode state, position by position).
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.model import model as M


def teacher_forced_logits(params, cfg, seqs: Sequence[np.ndarray],
                          lens: Sequence[int], new: int, *, slots: int,
                          max_len: int,
                          after_admit: Callable | None = None) -> list:
    """Admit ``seqs[i][:lens[i]]`` into row ``i`` in one masked call
    (prompts padded to a multiple of 32), then step each row through
    ``seqs[i][lens[i]:lens[i] + new - 1]`` one token a call.  Rows past
    ``len(seqs)`` stay empty.  ``after_admit(state) -> state``, if given,
    is applied between the admission and the first step.

    Returns each row's ``(new, vocab_size)`` logits: the prediction
    after the prompt and after each forced token."""
    step = jax.jit(lambda p, st, tok, ln, m: M.decode_step(
        p, cfg, st, tok, ln, token_mask=m, last_only=True, max_len=max_len),
        donate_argnums=(1,))
    width = -(-max(lens) // 32) * 32
    tokens = np.zeros((slots, width), np.int32)
    mask = np.zeros((slots, width), bool)
    for i, (s, n) in enumerate(zip(seqs, lens)):
        tokens[i, :n], mask[i, :n] = s[:n], True
    state = M.init_decode_state(cfg, slots, max_len, insert_window=width)
    logits, state = step(params, state, jnp.asarray(tokens), jnp.int32(0),
                         jnp.asarray(mask))
    if after_admit is not None:
        state = after_admit(state)
    rows = range(len(seqs))
    out = [[logits[i, 0]] for i in rows]
    live = jnp.asarray(np.arange(slots)[:, None] < len(seqs))
    for j in range(new - 1):
        cur = np.zeros((slots, 1), np.int32)
        at = np.zeros(slots, np.int32)
        for i, (s, n) in enumerate(zip(seqs, lens)):
            cur[i, 0], at[i] = s[n + j], n + j
        logits, state = step(params, state, jnp.asarray(cur),
                             jnp.asarray(at), live)
        for i in rows:
            out[i].append(logits[i, 0])
    del state
    return [jnp.stack(o)[:, :cfg.vocab_size].astype(jnp.float32)
            for o in out]
