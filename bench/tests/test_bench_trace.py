"""The trace reduction on a short trace recorded on the chip: the chat
cell on one TPU v5 lite, two scheduler steps (``data/chat_small.xplane.pb.gz``,
recorded by ``python bench/run.py --workload rwkv6-1.6b.chat --seconds 1.5
--trace 1 --trace-dir ...``)."""

import gzip

import pytest

import bench_testlib
from bench import flops, trace_reduce

DATA = bench_testlib.REPO / "bench" / "tests" / "data" / "chat_small.xplane.pb.gz"


@pytest.fixture(scope="module")
def data():
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(gzip.open(DATA).read())


@pytest.fixture(scope="module")
def summary(data):
    return trace_reduce.reduce(data)


def _plane(data, name):
    return next(p for p in data.planes if p.name == name)


def _window(data):
    steps = [e for p in data.planes if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events if e.name == "bench.step"]
    return min(e.start_ns for e in steps), max(e.end_ns for e in steps)


def test_busy_is_the_union_of_device_ops(data, summary):
    w0, w1 = _window(data)
    ops = next(ln for ln in _plane(data, "/device:TPU:0").lines
               if ln.name == "XLA Ops")
    # Walk the ops in time order, counting each instant once.
    busy, reach = 0, w0
    for s, e in sorted((ev.start_ns, ev.end_ns) for ev in ops.events):
        s, e = max(s, reach), min(e, w1)
        if e > s:
            busy += e - s
            reach = e
    assert summary.window_s == pytest.approx((w1 - w0) * 1e-9)
    assert summary.busy_s == pytest.approx(busy * 1e-9)
    assert summary.window_s == pytest.approx(0.870326032)
    assert 1 - summary.busy_s / summary.window_s == pytest.approx(
        0.021386, abs=1e-5)


def test_program_device_time(data, summary):
    mods = next(ln for ln in _plane(data, "/device:TPU:0").lines
                if ln.name == "XLA Modules")
    admit = [e.duration_ns * 1e-9 for e in mods.events
             if e.name.startswith("jit_admit")]
    win = [e.duration_ns * 1e-9 for e in mods.events
           if e.name.startswith("jit_win")]
    assert summary.program_times("admit") == pytest.approx(admit)
    assert summary.program_times("window") == pytest.approx(win)
    assert len(admit) == 2 and len(win) == 2
    assert sum(admit) == pytest.approx(0.597631935)
    assert sum(win) == pytest.approx(0.254090011)


def test_kernel_calls_and_their_shapes(summary):
    calls = summary.kernel_calls("wkv")
    by = {}
    for c in calls:
        key = (c.name, c.operands[0][1][2])
        by[key] = by.get(key, 0) + 1
        assert len(c.operands) == 6 and len(c.results) == 2
        assert c.results[1] == ("f32", (32, 32, 64, 64))
    # 24 layers: two windows of K=8 single-token decode calls; two
    # admissions through the chunked kernel at buckets 160 and 256.
    assert by == {("wkv_decode_pallas", 1): 384, ("wkv_pallas", 160): 24,
                  ("wkv_pallas", 256): 24}
    assert sum(c.seconds for c in calls) == pytest.approx(0.341602019)
    least = sum(max(f / 197e12, b / 819e9) for f, b in
                (flops.wkv_call_cost(c.operands, c.results) for c in calls))
    assert 0 < least < sum(c.seconds for c in calls)


def test_breakdown_names_ops_and_gaps(summary):
    b = summary.breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "wkv_pallas" and "wkv_decode_pallas" in names
    assert "while" not in names and len(names) <= 10
    assert len(b["idle_gaps"]) <= 10
    assert all(label.startswith("bench.") and s >= 1e-6
               for label, s in b["idle_gaps"])
    assert sum(s for s, _ in summary.gaps) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-3)


def test_parse_shapes():
    text = ("%wkv_pallas.5 = (bf16[2,3,8,64]{3,2,1,0:T(8,128)(2,1)}, "
            "f32[2,3,64,64]{3,2,1,0}) custom-call(bf16[2,3,8,64]{3,2,1,0:"
            "T(8,128)(2,1)} %a, f32[2,3,64,64]{3,2,1,0} %b), "
            "custom_call_target=\"tpu_custom_call\", operand_layout_"
            "constraints={bf16[9,9]{1,0}}")
    res, opr = trace_reduce.parse_shapes(text)
    assert res == [("bf16", (2, 3, 8, 64)), ("f32", (2, 3, 64, 64))]
    assert opr == [("bf16", (2, 3, 8, 64)), ("f32", (2, 3, 64, 64))]
    assert trace_reduce.op_name(text) == "wkv_pallas"
