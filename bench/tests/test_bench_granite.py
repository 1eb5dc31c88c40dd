"""The granite-4.0-h-micro cell's readers and its run at a tiny size.

``ssd_roofline`` and ``hybrid_step_mfu`` carry their own cost functions;
they are checked here from shapes against hand counts, against
``bench/flops.py`` where the two must agree (a model of attention layers
only), and for silence where a trace holds nothing of theirs: a summary
without the SSD ops (the parent program, or another model)."""

import json
import types

import pytest

import bench_testlib
from bench import flops, harness, trace_reduce
from bench_testlib import REPO, load_json

#: The tiny stand-in: one ten-layer period (9 Mamba, 1 attention),
#: admitting into two of the tiny mix's four slots at a time, as the
#: cell admits into four of its 32.
TINY = dict(num_layers=10, d_model=128, num_heads=2, num_kv_heads=1,
            head_dim=64, d_ff=256, vocab_size=512, mamba_heads=8,
            mamba_head_dim=16, mamba_d_state=16, admit_rows=2)


def _reader(name):
    return harness.load_module(REPO / "bench" / "metrics" / f"{name}.py")


def _sizes(config, **over):
    cfg = load_json(REPO / "bench" / "configs" / f"{config}.json")
    cfg["model"].update(over)
    return harness.sizes_of(harness.arch_config(cfg))


def _summary(op_time, admit=(), window=()):
    return trace_reduce.Summary(
        window_s=1.0, busy_s=1.0,
        programs={"admit": list(admit), "window": list(window)},
        op_time=op_time, kernels={}, gaps=[], host_span=(0.0, 10.0))


def _ctx(summary, steps, sizes):
    return types.SimpleNamespace(
        trace=summary, served=types.SimpleNamespace(steps=steps), sizes=sizes,
        peaks=harness.peaks_for(REPO, "TPU v5 lite"))


STEPS = [{"t_start": 1.0, "t_end": 2.0, "prompts": [300], "positions": []},
         {"t_start": 2.0, "t_end": 3.0, "prompts": [], "positions": [300, 41]}]


def test_ssd_costs_from_shapes():
    r = _reader("ssd_roofline")
    sizes = _sizes("granite-4.0-h-micro")
    f, b = r.token_cost(sizes)
    state = 64 * 64 * 128
    assert f == 36 * 5 * state
    assert b == 36 * (2 * state * 4 + 64 * 64 * 2 * 2 + 2 * 128 * 2 + 64 * 4)
    f, b = r.prompt_cost(sizes, 300)     # chunks of 128, 128 and 44 tokens
    per = sum(2 * l * l * 128 + 64 * (2 * l * l * 64 + 4 * l * 128 * 64)
              for l in (128, 128, 44))
    assert f == 36 * per
    assert b == 36 * (300 * (64 * 64 * 2 * 2 + 2 * 128 * 2 + 64 * 4)
                      + state * 4)


def test_ssd_roofline_reads_the_ssd_ops_only():
    r = _reader("ssd_roofline")
    sizes = _sizes("granite-4.0-h-micro")
    assert r.read(_ctx(_summary({"fusion": 1.0}), STEPS, sizes)) is None
    assert r.read(_ctx(_summary({"ssd_chunk_pallas": 0.5}),
                       STEPS, _sizes("qwen2-0.5b"))) is None
    peaks = harness.peaks_for(REPO, "TPU v5 lite")
    f, b = r.prompt_cost(sizes, 300)
    tf, tb = r.token_cost(sizes)
    least = (max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
             + 2 * max(tf / peaks["bf16_flops_per_s"],
                       tb / peaks["hbm_bytes_per_s"]))
    got = r.read(_ctx(_summary({"ssd_chunk_pallas": 0.01,
                                "ssd_decode_window_pallas": 0.02}),
                      STEPS, sizes))
    assert got == pytest.approx(100 * least / 0.03)


def test_hybrid_flops_equal_step_flops_for_attention_only():
    """On a model of attention layers only the per-kind count is the
    count ``step_mfu`` makes."""
    r = _reader("hybrid_step_mfu")
    sizes = _sizes("qwen2-0.5b")
    for p in (1, 77, 511):
        assert r.decode_flops(sizes, p) == flops.decode_flops(sizes, p)
    assert r.prompt_flops(sizes, 64) == flops.prompt_flops(sizes, 64)


def test_hybrid_flops_by_layer_kind():
    r = _reader("hybrid_step_mfu")
    sizes = _sizes("granite-4.0-h-micro")
    d, f = 2048, 8192
    mamba = (2 * 3 * d * f + 2 * d * (4096 + 4352 + 64) + 2 * 4 * 4352
             + 5 * 64 * 64 * 128 + 2 * 4096 * d)
    attn = 2 * 3 * d * f + 2 * d * 64 * (2 * 32 + 2 * 8) + 4 * 32 * 64 * 10
    assert r.token_flops(sizes, 10) == 36 * mamba + 4 * attn
    s = _summary({}, admit=[0.5], window=[0.25])
    got = r.read(_ctx(s, STEPS, sizes))
    work = r.prompt_flops(sizes, 300) + sum(r.decode_flops(sizes, q)
                                            for q in (300, 41))
    assert got == pytest.approx(100 * work / (0.75 * 197e12))
    assert r.read(_ctx(_summary({}), STEPS, sizes)) is None


@pytest.mark.parametrize("cell", ["granite-4.0-h-micro.chat",
                                  "qwen2-0.5b.short_chat"])
def test_new_cell_runs_correct_at_a_tiny_size(tmp_path, monkeypatch, cell):
    monkeypatch.setitem(bench_testlib.TINY_MODEL, "granite-4.0-h-micro", TINY)
    r = bench_testlib.run_tiny(tmp_path, cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    json.dumps(r)
