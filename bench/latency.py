"""Latency arithmetic over all requests of a run (host clock).

Every request counts: a percentile is taken over all of them, never over
chunks.  A request that never reached the point measured (no first token,
never admitted, or unfinished at the drain's cap) is a miss: it ranks
above every measured value, and where the percentile lands on a miss the
value reported is the drain cap itself.  The harness's own pauses (the
profiler starting and stopping in a ``--trace 1`` run) are left out of
every interval they fall in: they are not the system's time.
"""

from __future__ import annotations

import math

import numpy as np

from bench.harness import DRAIN_CAP_S


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile: the smallest value with at least
    ``q`` percent of all values at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


def tail_ms(values_s, q: float) -> float | None:
    """``q``-th percentile in ms of per-request seconds, NaN = miss."""
    vals = np.asarray(values_s, float)
    if vals.size == 0:
        return None
    vals = np.where(np.isnan(vals), np.inf, vals)
    return 1e3 * min(percentile(vals, q), DRAIN_CAP_S)


def held_s(served, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``b - a`` less the harness's pauses that fall between them."""
    d = b - a
    for p0, p1 in served.pauses:
        d = d - np.clip(np.minimum(b, p1) - np.maximum(a, p0), 0.0, None)
    return d


def ttft_s(served) -> np.ndarray:
    """Due time to first token, for every request handed over."""
    i = served.attempted()
    return held_s(served, served.due[i], served.t_first[i])


def queue_wait_s(served) -> np.ndarray:
    """Due time to the start of the step that admitted the request."""
    i = served.attempted()
    return held_s(served, served.due[i], served.admit_start[i])


def tpot_s(served) -> np.ndarray:
    """(last - first token) / (tokens - 1) per request handed over with a
    budget of two or more; NaN where it did not finish."""
    i = served.attempted()
    i = i[served.budget[i] >= 2]
    done = np.array([served.outcome[j] in ("ok", "eos") for j in i], bool)
    n = np.array([served.outputs[j].size for j in i], float)
    out = held_s(served, served.t_first[i], served.t_last[i]) / np.maximum(n - 1, 1)
    return np.where(done & (n >= 2), out, np.nan)
