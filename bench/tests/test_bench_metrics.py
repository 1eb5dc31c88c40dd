"""Metric arithmetic over a synthetic run: percentiles over all requests,
misses in the tail, rates over the whole window, and the readers."""

import math

import numpy as np
import pytest

import bench_testlib  # noqa: F401
from bench import flops, harness, latency
from bench_testlib import REPO


def served(n=20, t0=100.0):
    """n requests due 0.1 s apart; request i's first token 0.05*(i+1) s
    after its due time, then 9 more tokens 0.01 s apart."""
    due = t0 + 0.1 * np.arange(n)
    first = due + 0.05 * (np.arange(n) + 1)
    return harness.Served(
        t0=t0, t_close=t0 + 10.0, due=due, handed=due.copy(),
        admit_start=first - 0.02, t_first=first, t_last=first + 0.09,
        budget=np.full(n, 10),
        prompt_len=np.full(n, 32), outcome=["ok"] * n,
        outputs=[np.zeros(10, np.int32)] * n,
        prompts=[np.zeros(32, np.int32)] * n, steps=[],
        tokens_in_window=200)


def test_percentile_nearest_rank_over_all_values():
    v = list(range(1, 101))
    assert latency.percentile(v, 90) == 90
    assert latency.percentile(v, 50) == 50
    assert latency.percentile([5.0], 90) == 5.0
    # Over all values, not the mean of per-chunk percentiles.
    chunks = [v[:50], v[50:]]
    assert latency.percentile(v, 90) != np.mean(
        [latency.percentile(c, 90) for c in chunks])


def test_ttft_and_queue_wait_tails():
    s = served()
    # TTFT of request i is 0.05*(i+1): p90 of 20 is the 18th = 0.9 s.
    assert latency.tail_ms(latency.ttft_s(s), 90) == pytest.approx(900.0)
    assert latency.tail_ms(latency.queue_wait_s(s), 90) == pytest.approx(880.0)


def test_tpot_and_misses():
    s = served()
    assert latency.tail_ms(latency.tpot_s(s), 90) == pytest.approx(10.0)
    # Three unfinished requests: they rank above every measured value.
    for i in (0, 1, 2):
        s.outcome[i] = None
    vals = latency.tpot_s(s)
    assert np.isnan(vals[:3]).all()
    assert latency.tail_ms(vals, 90) == harness.DRAIN_CAP_S * 1e3
    # Requests never given a first token are TTFT misses too.
    s.t_first[5] = s.t_first[6] = np.nan
    assert latency.tail_ms(latency.ttft_s(s), 95) == harness.DRAIN_CAP_S * 1e3


def _ctx(s):
    _, cell = harness.find_cell(REPO, "rwkv6-1.6b.doc_batch")
    return harness.Context(cell=cell, served=s, sizes={}, peaks={},
                           setup_s=12.5)


def test_rate_over_whole_window_and_setup():
    s = served()
    ctx = _ctx(s)
    tok = harness.load_module(REPO / "bench" / "metrics" / "tokens_per_s.py")
    assert tok.read(ctx) == pytest.approx(200 / 10.0)
    setup = harness.load_module(REPO / "bench" / "metrics" / "setup_s.py")
    assert setup.read(ctx) == 12.5


def test_model_flops_from_shapes():
    rwkv = dict(rwkv=True, d_model=2048, d_ff=7168, head_dim=64,
                num_layers=24, vocab_size=65536)
    per_tok = 24 * (2 * (5 * 2048**2 + 2 * 2048 * 64 + 3 * 2048 * 7168)
                    + 5 * 32 * 64 * 64)
    assert flops.layer_flops(rwkv, 1) == per_tok
    assert flops.layer_flops(rwkv, 1000) == per_tok   # no attention term
    qwen = dict(d_model=896, d_ff=4864, num_heads=14, num_kv_heads=2,
                head_dim=64, num_layers=24, vocab_size=151936)
    grow = flops.layer_flops(qwen, 101) - flops.layer_flops(qwen, 100)
    assert grow == 24 * 4 * 14 * 64
    assert flops.prompt_flops(qwen, 3) == sum(
        flops.layer_flops(qwen, c) for c in (1, 2, 3)) + 2 * 896 * 151936
    # About 2 x parameters per token: 1.6B parameters -> ~3 GFLOP.
    assert 2.5e9 < flops.decode_flops(rwkv, 10) < 3.6e9


def test_wkv_call_cost_counts_state_once():
    b, h, t, dh = 32, 32, 8, 64
    io = [("bf16", (b, h, t, dh))] * 4 + [("bf16", (h, 1, dh)),
                                         ("f32", (b, h, dh, dh))]
    res = [("bf16", (b, h, t, dh)), ("f32", (b, h, dh, dh))]
    f, nbytes = flops.wkv_call_cost(io, res)
    assert f == 5 * b * h * t * dh * dh
    state = b * h * dh * dh * 4
    assert nbytes == 2 * state + 5 * b * h * t * dh * 2 + h * dh * 2
    assert math.isclose(nbytes, 2 * state, rel_tol=0.2)


def test_tracer_keeps_an_admitting_step(monkeypatch):
    """The profiler stops only once the window has closed and an admitting
    step is in the stretch; its start and stop are kept as pauses."""
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    clock = iter(float(x) for x in range(100, 200))
    t = harness.Tracer("unused", start_at=1.0, clock=lambda: next(clock))
    t.before_step(0.5)
    assert calls == []
    t.before_step(1.0)
    t.after_step(1.0, 2.0, admitted=True)   # admitted, window still open
    t.after_step(2.0, 4.0)
    assert calls == ["start"] and t.span is None
    t.after_step(4.0, 4.0, closed=True)     # the close stops it
    assert calls == ["start", "stop"] and t.span == (1.0, 4.0)
    assert t.pauses == [(100.0, 101.0), (102.0, 103.0)]
    t.before_step(6.0)                      # traces once only
    assert calls == ["start", "stop"]
    u = harness.Tracer("unused", start_at=0.0)
    u.before_step(0.0)
    u.after_step(0.0, 1.0, closed=True)     # closed, no admit yet: on
    assert u.on
    u.after_step(1.0, 2.0, admitted=True, closed=True)
    assert u.span == (0.0, 2.0)
    v = harness.Tracer("unused", start_at=0.0)
    v.before_step(0.0)
    v.after_step(0.0, 1.0, closed=True)
    v.stop(3.0)                             # the drain ended first
    assert v.span == (0.0, 3.0) and not v.on


def test_latencies_leave_out_the_profilers_pauses():
    s = served()
    base_ttft = latency.ttft_s(s)
    base_wait = latency.queue_wait_s(s)
    base_tpot = latency.tpot_s(s)
    # A 5-s pause before every request's due time changes nothing.
    s.pauses = [(90.0, 95.0)]
    np.testing.assert_allclose(latency.ttft_s(s), base_ttft)
    # A pause inside request 19's wait (due 101.9, first token 102.9) is
    # taken out of it, and only of the part it overlaps.
    s.pauses = [(102.0, 102.5)]
    ttft = latency.ttft_s(s)
    assert ttft[19] == pytest.approx(base_ttft[19] - 0.5)
    assert latency.queue_wait_s(s)[19] == pytest.approx(base_wait[19] - 0.5)
    assert ttft[0] == pytest.approx(base_ttft[0])
    # Inside request 19's decode (102.9 -> 102.99): 0.04 s of it.
    s.pauses = [(102.95, 110.0)]
    assert latency.tpot_s(s)[19] == pytest.approx(base_tpot[19] - 0.04 / 9)
    # A miss stays a miss.
    s.t_first[3] = np.nan
    assert np.isnan(latency.ttft_s(s)[3])
