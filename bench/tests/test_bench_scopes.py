"""The scoped trace reduction (``bench/scopes.py``) and the readers that
need it.

Two traces recorded on one TPU v5 lite: ``chat_small`` (the program
before it had scopes or serve spans) must reduce exactly as the base
reduction does, with every new reader silent; ``long_decode_scoped``
carries the program's scopes and spans.  It is a traced run of the
``qwen2-0.5b.long_decode`` cell (51 s, ``--trace 1 --trace-dir ...``)
cut to two of its ``bench.step`` spans, each holding one admit and one
window: events that overlap them are kept with the metadata they use,
the planes no reduction reads are left out, and the harness's step
records of those two steps lie beside it."""

import gzip
import json
import types

import pytest

import bench_testlib
from bench import harness, scopes, trace_reduce

DATA = bench_testlib.REPO / "bench" / "tests" / "data"
METRICS = ("state_check_share", "admit_fill", "attention_roofline")


def _reader(name):
    return harness.load_module(bench_testlib.REPO / "bench" / "metrics"
                               / f"{name}.py")


def _ctx(summary, steps=(), config="qwen2-0.5b"):
    cfg = bench_testlib.load_json(bench_testlib.REPO / "bench" / "configs"
                                  / f"{config}.json")
    return types.SimpleNamespace(
        trace=summary, served=types.SimpleNamespace(steps=list(steps)),
        sizes=harness.sizes_of(harness.arch_config(cfg)),
        peaks=harness.peaks_for(bench_testlib.REPO, "TPU v5 lite"))


# --------------------------------------------------------------------------
# A trace of the program without scopes: nothing changes
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def unscoped():
    from jax.profiler import ProfileData

    raw = gzip.open(DATA / "chat_small.xplane.pb.gz").read()
    return (trace_reduce.reduce(ProfileData.from_serialized_xspace(raw)),
            scopes.reduce(raw))


def test_without_scopes_the_summary_is_the_base(unscoped):
    base, scoped = unscoped
    for f in ("window_s", "busy_s", "programs", "op_time", "gaps",
              "host_span"):
        assert getattr(scoped, f) == getattr(base, f), f
    assert scoped.kernels.keys() == base.kernels.keys()
    assert scoped.breakdown() == base.breakdown()
    assert "device_scopes" not in scoped.breakdown()
    assert scoped.scope_time == {} and scoped.spans == []


@pytest.mark.parametrize("name", METRICS)
def test_new_readers_are_silent_without_scopes(unscoped, name):
    assert _reader(name).read(_ctx(unscoped[1], config="rwkv6-1.6b")) is None


# --------------------------------------------------------------------------
# Pieces
# --------------------------------------------------------------------------

@pytest.mark.parametrize("path, scope", [
    ("jit(win)/while/body/model.mixer.attn/model.attn.decode/exp:",
     "model.attn.decode"),
    ("jit(admit)/serve.state_check/reduce_sum:", "serve.state_check"),
    ("jit(admit)/while/body/closed_call/model.mixer.rwkv/checkpoint/"
     "jit(wkv_pallas)/pallas_call:", "model.mixer.rwkv"),
    ("jit(admit)/jit(_where)/select_n:", None),
    ("jit(win)/model.head.extra/dot_general:", None),
])
def test_innermost_scope(path, scope):
    assert scopes.innermost_scope(path) == scope


def _plane(space, name, modules, ops):
    """A device plane: ``modules`` and ``ops`` are (name, tf_op, start ns,
    duration ns); lines start at t=1000 ns."""
    plane = space.planes.add(name=name.encode())
    st = plane.stat_metadata.add(key=7)
    st.value.id, st.value.name = 7, b"tf_op"
    for line_name, events in (("XLA Modules", modules), ("XLA Ops", ops)):
        line = plane.lines.add(name=line_name.encode(), timestamp_ns=1000)
        for ev_name, tf_op, start, dur in events:
            key = len(plane.event_metadata) + 1
            md = plane.event_metadata.add(key=key).value
            md.id, md.name = key, ev_name.encode()
            if tf_op:
                md.stats.add(metadata_id=7, str_value=tf_op.encode())
            line.events.add(metadata_id=key, offset_ps=(start - 1000) * 1000,
                            duration_ps=dur * 1000)


def test_scope_time_attributes_each_op():
    space = scopes._xspace_class()()
    ops = [("%fusion.1 = f32[2] fusion(...)",
            "jit(win)/while/body/model.mixer.attn/model.attn.decode/exp:",
            1100, 50),
           ("%while.3 = (...) while(...)", "jit(win)/while:", 1100, 300),
           ("%reduce.2 = u32[2] reduce(...)",
            "jit(win)/serve.state_check/reduce_sum:", 1380, 40),
           ("%add.4 = f32[2] add(...)", "jit(admit)/add:", 1600, 10),
           ("%copy.5 = f32[2] copy(...)", "jit(admit)/model.head/dot:",
            1700, 100)]
    _plane(space, "/device:TPU:0",
           [("jit_win(11)", "", 1050, 400), ("jit_admit(12)", "", 1550, 300)],
           ops)
    _plane(space, "/device:TPU:1", [("jit_win(11)", "", 1050, 400)],
           ops[:3])
    # The window ends at 1400 ns: the check is clipped to 20 ns; the
    # admit lies outside it.  Containers count nothing; two chips average.
    got = scopes.scope_time(space, 1000, 1400)
    assert got == {"window": {"model.attn.decode": pytest.approx(50e-9),
                              "serve.state_check": pytest.approx(20e-9)}}
    got = scopes.scope_time(space, 1000, 2000)
    assert got["admit"] == {"model.head": pytest.approx(50e-9)}


def test_gap_takes_the_innermost_enclosing_span():
    spans = [scopes.Span("serve.step", 0, 100, {}),
             scopes.Span("serve.admit", 10, 50, {}),
             scopes.Span("serve.admit.sync", 20, 30, {})]
    base = [(3e-9, "bench.step"), (2e-9, "bench.step"),
            (1e-9, "bench.collect")]
    mids = [(3e-9, 25), (2e-9, 60), (1e-9, 150)]
    assert scopes.label_gaps(base, mids, spans) == [
        (3e-9, "serve.admit.sync"), (2e-9, "serve.step"),
        (1e-9, "bench.collect")]


def test_decode_attention_cost():
    cost = _reader("attention_roofline").decode_attention_cost
    qwen = _ctx(None).sizes
    f, b = cost(qwen, 99)
    assert f == 24 * 4 * 14 * 64 * 100
    assert b == 24 * 2 * 2 * 64 * 100 * 2
    # Local layers attend within their window; recurrent layers not at all.
    sizes = dict(qwen, num_layers=7, attn_window=16,
                 pattern=("local", "local", "global", "rec"))
    f, _ = cost(sizes, 99)
    assert f == 4 * 14 * 64 * (4 * 16 + 2 * 100)


# --------------------------------------------------------------------------
# A trace of the program with scopes and spans, recorded on the chip
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def scoped():
    raw = gzip.open(DATA / "long_decode_scoped.xplane.pb.gz").read()
    rec = json.loads((DATA / "long_decode_scoped.steps.json").read_text())
    summary = scopes.reduce(raw, tuple(rec["traced"]))
    return summary, rec["steps"]


def test_scope_time_fits_in_each_program(scoped):
    s, _ = scoped
    for kind in ("admit", "window"):
        assert 0 < sum(s.scope_time[kind].values()) <= sum(
            s.program_times(kind))
    assert s.scope_seconds("serve.state_check") > 0
    assert s.scope_time["window"]["model.attn.decode"] > 0
    assert "serve.reset_rows" not in s.scope_time["window"]
    assert set(s.scope_time["admit"]) <= set(scopes.SCOPES)


def test_breakdown_adds_device_scopes(scoped):
    s, _ = scoped
    b = s.breakdown()
    assert list(b)[:2] == ["device_ops", "idle_gaps"]
    names = [n for n, _ in b["device_scopes"]]
    assert 0 < len(names) <= 10
    assert all(n.split("/", 1)[1] in scopes.SCOPES for n in names)
    secs = [t for _, t in b["device_scopes"]]
    assert secs == sorted(secs, reverse=True)


def test_gaps_are_labelled_by_serve_spans(scoped):
    s, _ = scoped
    labels = [label for label, _ in s.breakdown()["idle_gaps"]]
    assert labels and all(label.startswith("serve.") for label in labels)


def test_spans_carry_their_arguments(scoped):
    s, _ = scoped
    admits = s.span_args("serve.admit")
    assert admits and all(a["slots"] == 32 and a["bucket"] % 32 == 0
                          and 0 < a["prompt_tokens"] <= a["rows"] * a["bucket"]
                          for a in admits)
    windows = s.span_args("serve.window")
    assert len(windows) == len(s.program_times("window"))
    assert all(a["k"] == 16 and 0 < a["active"] <= 32 for a in windows)


@pytest.mark.parametrize("name", METRICS)
def test_new_readers_read_the_scoped_trace(scoped, name):
    s, steps = scoped
    v = _reader(name).read(_ctx(s, steps))
    assert v is not None and 0 < v < 100
