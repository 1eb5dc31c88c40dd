"""The traffic generator: seeds, rates, clipping and the fixed work."""

import numpy as np
import pytest

import bench_testlib  # noqa: F401  (puts the repo on the path)
from bench import traffic
from bench_testlib import REPO, load_json

CHAT = load_json(REPO / "bench" / "traffic" / "rwkv6-1.6b.chat.json")
DOC = load_json(REPO / "bench" / "traffic" / "rwkv6-1.6b.doc_batch.json")


def test_same_seed_same_schedule():
    a = traffic.generate(CHAT, 2**32 + 17, 20.0, 65536)
    b = traffic.generate(CHAT, 2**32 + 17, 20.0, 65536)
    np.testing.assert_array_equal(a.due_s, b.due_s)
    np.testing.assert_array_equal(a.prompt_lens, b.prompt_lens)
    np.testing.assert_array_equal(a.output_lens, b.output_lens)
    for x, y in zip(a.prompts, b.prompts):
        np.testing.assert_array_equal(x, y)


def test_seeds_reorder_the_same_work():
    a = traffic.generate(CHAT, 1, 20.0, 65536)
    b = traffic.generate(CHAT, 2, 20.0, 65536)
    assert not np.array_equal(a.prompt_lens, b.prompt_lens)
    np.testing.assert_array_equal(np.sort(a.prompt_lens), np.sort(b.prompt_lens))
    np.testing.assert_array_equal(np.sort(a.output_lens), np.sort(b.output_lens))
    np.testing.assert_allclose(np.sort(np.diff(a.due_s)).sum(),
                               np.sort(np.diff(b.due_s)).sum(), rtol=0.05)


@pytest.mark.parametrize("seconds", [10.0, 51.0])
def test_rate_and_clipping_honoured(seconds):
    s = traffic.generate(CHAT, 99, seconds, 65536)
    rate = CHAT["arrivals"]["rate_per_s"]
    assert len(s) == round(rate * seconds)
    assert s.due_s[0] == 0.0 and np.all(np.diff(s.due_s) >= 0)
    assert s.due_s[-1] < seconds
    # Mean inter-arrival gap within 5% of 1 / rate.
    assert abs(np.mean(np.diff(s.due_s)) * rate - 1.0) < 0.05
    p, o = CHAT["prompt_tokens"], CHAT["output_tokens"]
    assert s.prompt_lens.min() >= p["min"] and s.prompt_lens.max() <= p["max"]
    assert s.output_lens.min() >= o["min"] and s.output_lens.max() <= o["max"]
    assert abs(np.median(s.prompt_lens) - p["median"]) <= 2
    assert all(x.size == n for x, n in zip(s.prompts, s.prompt_lens))
    assert all(x.min() >= 0 and x.max() < 65536 for x in s.prompts)


def test_saturated_and_uniform():
    s = traffic.generate(DOC, 5, 10.0, 65536)
    assert len(s) == DOC["arrivals"]["requests"]
    assert np.all(s.due_s == 0.0)
    o = DOC["output_tokens"]
    assert s.output_lens.min() == o["min"] and s.output_lens.max() == o["max"]


def test_admit_buckets_cover_every_prompt_length():
    buckets = traffic.admit_buckets(CHAT)
    assert buckets == list(range(32, 257, 32))
    for n in range(CHAT["prompt_tokens"]["min"], CHAT["prompt_tokens"]["max"] + 1):
        assert -(-n // 32) * 32 in buckets
