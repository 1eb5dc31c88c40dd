"""Traffic generator: one cell's request schedule from its mix file and a seed.

A mix file (``bench/traffic/<workload>.json``) holds parameters only:
arrival process and rate, prompt and output length distributions, the
serving settings and the correctness sample size.  Every seed gets the
same multiset of sizes and inter-arrival gaps (stratified quantiles of
the stated distributions), in an order and with prompt tokens drawn from
the seed, so runs with different seeds do the same amount of work.
"""

from __future__ import annotations

import dataclasses

import numpy as np

@dataclasses.dataclass(frozen=True)
class Schedule:
    """Requests of one run, in due order."""

    due_s: np.ndarray            # (N,) seconds after the window opens
    prompt_lens: np.ndarray      # (N,) int
    output_lens: np.ndarray      # (N,) int (greedy, no EOS: exact)
    prompts: list                # N int32 arrays

    def __len__(self) -> int:
        return int(self.due_s.size)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the stratified quantiles of ``spec``, clipped to
    ``[min, max]``.  ``lognormal`` takes ``median`` and ``sigma``;
    ``uniform`` is over the integers ``min..max``."""
    lo, hi = int(spec["min"]), int(spec["max"])
    q = _quantiles(n)
    if spec["dist"] == "lognormal":
        from statistics import NormalDist

        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        vals = np.rint(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
    elif spec["dist"] == "uniform":
        vals = lo + np.floor(q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(vals, lo, hi).astype(np.int64)


def bucket_bounds(spec: dict) -> tuple[int, int]:
    """Smallest and largest length a distribution can produce."""
    return int(spec["min"]), int(spec["max"])


def request_count(mix: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["process"] == "poisson":
        return max(1, int(round(float(arr["rate_per_s"]) * seconds)))
    if arr["process"] == "saturated":
        return int(arr["requests"])
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> Schedule:
    """The schedule of one run: ``seed`` orders the fixed sizes and gaps
    and draws the prompt tokens."""
    n = request_count(mix, seconds)
    rng = np.random.default_rng(seed)
    p_lens = rng.permutation(lengths(mix["prompt_tokens"], n))
    o_lens = rng.permutation(lengths(mix["output_tokens"], n))
    arr = mix["arrivals"]
    if arr["process"] == "poisson":
        rate = float(arr["rate_per_s"])
        gaps = rng.permutation(-np.log1p(-_quantiles(n)) / rate)
        due = np.cumsum(gaps) - gaps[0]
    else:
        due = np.zeros(n)
    prompts = [rng.integers(0, vocab, int(p), dtype=np.int32) for p in p_lens]
    return Schedule(due_s=due, prompt_lens=p_lens, output_lens=o_lens,
                    prompts=prompts)


def admit_buckets(mix: dict) -> list[int]:
    """Every 32-token admit bucket the mix's prompt lengths can produce."""
    lo, hi = bucket_bounds(mix["prompt_tokens"])
    first = -(-lo // 32) * 32
    return list(range(first, -(-hi // 32) * 32 + 1, 32))


def worst_total(mix: dict) -> int:
    """Largest prompt + output length the mix can produce."""
    return (bucket_bounds(mix["prompt_tokens"])[1]
            + bucket_bounds(mix["output_tokens"])[1])

