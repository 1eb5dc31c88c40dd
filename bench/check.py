"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample
of the finished requests, drawn from the seed and always holding the
longest, is run once through the configuration's plain reference:
prompt plus served tokens, teacher-forced.  At every served token the
reference's logits say how far the served token lies below the
reference's best, in units of that position's logit standard deviation
(``gap``).  Greedy serving in the stated precision keeps the widest gap
near bf16 rounding; a wrong token reads several standard deviations.

The control is the same reference with every matmul operand in float8
(``precision="fp8"``): at each position, the gap of the token the lower
precision puts first.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Rows of logits computed at once (keeps the (rows, vocab) block small).
ROW_BLOCK = 256


def pick_sample(finished: list[int], total_len, k: int, rng) -> list[int]:
    """``k`` finished request ids: the longest by ``total_len`` and the
    rest drawn by ``rng`` without replacement."""
    if not finished:
        return []
    longest = max(finished, key=lambda i: (total_len(i), -i))
    rest = [i for i in finished if i != longest]
    n = min(k - 1, len(rest))
    picked = rng.choice(len(rest), size=n, replace=False) if n else []
    return [longest] + sorted(rest[j] for j in picked)


@jax.jit
def _gap_stats(ref_logits, served):
    """Per row: (best - logit of ``served``) / std of the row."""
    best = jnp.max(ref_logits, axis=-1)
    std = jnp.maximum(jnp.std(ref_logits, axis=-1), 1e-30)
    got = jnp.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    return (best - got) / std


def _served_gaps(ref, params, sizes, seqs, pad_to, precision_pairs):
    """Gaps of each (hidden precision, head precision) pair's chosen tokens
    under the float32 reference.  ``seqs`` is a list of (prompt, served).
    Returns {name: (N,) gaps} with "served" for the served tokens and the
    pair names for the tokens each pair puts first."""
    b = len(seqs)
    tokens = np.zeros((b, pad_to), np.int32)
    rows_b, rows_t, served = [], [], []
    for i, (prompt, out) in enumerate(seqs):
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        tokens[i, : seq.size] = seq
        p = prompt.size
        rows_b += [i] * out.size
        rows_t += list(range(p - 1, p - 1 + out.size))
        served.append(out)
    n = sum(out.size for out in served)
    # Pad the rows to whole blocks (repeating row 0): one compiled head.
    pad = -n % ROW_BLOCK
    served = np.concatenate(served + [served[0][:1]] * pad).astype(np.int32)
    idx_b = np.asarray(rows_b + [rows_b[0]] * pad)
    idx_t = np.asarray(rows_t + [rows_t[0]] * pad)
    hid = {"f32": ref.hidden(params, sizes, jnp.asarray(tokens), "f32")}
    for name in precision_pairs:
        hid[name] = ref.hidden(params, sizes, jnp.asarray(tokens), name)
    rows = {k: v[idx_b, idx_t] for k, v in hid.items()}
    del hid
    head = jax.jit(lambda p, r, prec: ref.head(p, sizes, r, prec),
                   static_argnums=2)
    gaps = {"served": []}
    gaps.update({name: [] for name in precision_pairs})
    for s in range(0, served.size, ROW_BLOCK):
        sl = slice(s, s + ROW_BLOCK)
        ref_logits = head(params, rows["f32"][sl], "f32")
        gaps["served"].append(np.asarray(
            _gap_stats(ref_logits, jnp.asarray(served[sl]))))
        for name in precision_pairs:
            mine = jnp.argmax(head(params, rows[name][sl], name), axis=-1)
            gaps[name].append(np.asarray(
                _gap_stats(ref_logits, mine.astype(jnp.int32))))
    return {k: np.concatenate(v)[:n] for k, v in gaps.items()}


def compare(ref, params, sizes, seqs, pad_to, *, control=False):
    """Widest served-token gap (and, with ``control``, the control's) over
    ``seqs``.  ``pad_to`` fixes the reference's sequence length, so one
    compiled reference serves every run of a cell."""
    gaps = _served_gaps(ref, params, sizes, seqs, pad_to,
                        ("fp8",) if control else ())
    out = {"max_gap_sd": float(np.max(gaps["served"])),
           "tokens": int(gaps["served"].size)}
    if control:
        out["control_max_gap_sd"] = float(np.max(gaps["fp8"]))
    return out
