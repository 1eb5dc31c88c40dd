"""The program's own scopes and spans in a profiler trace: device time by
named scope, the ``serve.*`` host spans with their arguments, and idle
gaps labelled by the host phase they fall in.

    python -m bench.scopes <trace dir>      # the scoped breakdown

The program marks its layers with ``jax.named_scope`` (``SCOPES``) and
the phases of ``ServeSession.step`` with profiler spans: ``serve.step``
holds ``serve.admit`` and ``serve.window``, and each of those holds
``.prepare``, ``.dispatch``, ``.sync`` and ``.bookkeep``.  A scope
reaches the trace as the ``tf_op`` stat in each device op's metadata
(``jit(win)/while/body/model.mixer.attn/model.attn.decode/dot_general:``),
which ``jax.profiler.ProfileData`` does not expose; the XSpace proto is
parsed here against a schema of just the fields read, built in a private
descriptor pool.  An op counts under the innermost listed scope on its
path.  A fusion carries the metadata of its root op, so a fusion that
mixes scopes counts where XLA's root op puts it.

:func:`reduce` returns the :class:`bench.trace_reduce.Summary` of the
same trace, with the base reduction's fields as it computes them, plus
``scope_time`` and ``spans``.  Gaps keep their base label unless a
``serve.*`` span encloses their middle, and ``breakdown()`` gains
``device_scopes`` only where the trace has scopes: on a trace of a
program without them, the summary and its breakdown are the base's.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import dataclasses
import functools
import json

from bench import trace_reduce

#: The program's device scopes, as ``jax.named_scope`` names them.
SCOPES = ("serve.state_check", "serve.reset_rows", "serve.sample",
          "model.embed", "model.mixer.attn", "model.mixer.rwkv",
          "model.mixer.rglru", "model.attn.decode", "model.mlp",
          "model.head")
#: Host spans of the program (``ServeSession``) start with this.
SPAN_PREFIX = "serve."


# --------------------------------------------------------------------------
# XSpace: the fields read, and nothing else
# --------------------------------------------------------------------------

#: (message, [(field, number, type, repeated, message type)]) from
#: tsl/profiler/protobuf/xplane.proto.  Strings are read as bytes, so a
#: name that is not UTF-8 cannot stop the parse.
_SCHEMA = (
    ("XSpace", [("planes", 1, "message", True, "XPlane")]),
    ("XPlane", [("name", 2, "bytes", False, None),
                ("lines", 3, "message", True, "XLine"),
                ("event_metadata", 4, "message", True, "EventMetadataEntry"),
                ("stat_metadata", 5, "message", True, "StatMetadataEntry")]),
    ("EventMetadataEntry", [("key", 1, "int64", False, None),
                            ("value", 2, "message", False, "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, "int64", False, None),
                           ("value", 2, "message", False, "XStatMetadata")]),
    ("XLine", [("name", 2, "bytes", False, None),
               ("timestamp_ns", 3, "int64", False, None),
               ("events", 4, "message", True, "XEvent")]),
    ("XEvent", [("metadata_id", 1, "int64", False, None),
                ("offset_ps", 2, "int64", False, None),
                ("duration_ps", 3, "int64", False, None)]),
    ("XEventMetadata", [("id", 1, "int64", False, None),
                        ("name", 2, "bytes", False, None),
                        ("stats", 5, "message", True, "XStat")]),
    ("XStat", [("metadata_id", 1, "int64", False, None),
               ("str_value", 5, "bytes", False, None),
               ("ref_value", 7, "uint64", False, None)]),
    ("XStatMetadata", [("id", 1, "int64", False, None),
                       ("name", 2, "bytes", False, None)]),
)


@functools.lru_cache(maxsize=None)
def _xspace_class():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    fd = descriptor_pb2.FileDescriptorProto(
        name="bench/xplane_subset.proto", package="bench_xplane",
        syntax="proto3")
    F = descriptor_pb2.FieldDescriptorProto
    types = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
             "bytes": F.TYPE_BYTES, "message": F.TYPE_MESSAGE}
    for name, fields in _SCHEMA:
        msg = fd.message_type.add(name=name)
        for fname, number, typ, repeated, mtype in fields:
            f = msg.field.add(
                name=fname, number=number, type=types[typ],
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if mtype:
                f.type_name = f".bench_xplane.{mtype}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def parse_xspace(raw: bytes):
    """The XSpace in ``raw`` (a serialized ``.xplane.pb``), fields of
    ``_SCHEMA`` only."""
    space = _xspace_class()()
    space.ParseFromString(raw)
    return space


def _text(b: bytes) -> str:
    return b.decode("utf-8", "replace")


def innermost_scope(tf_op: str) -> str | None:
    """The last listed scope on an op-name path, or None."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        if part in SCOPES:
            return part
    return None


def _op_scopes(plane) -> dict:
    """metadata id -> (short op name, innermost scope or None)."""
    stat_names = {e.key: _text(e.value.name) for e in plane.stat_metadata}
    tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
    out = {}
    for e in plane.event_metadata:
        md = e.value
        path = ""
        for st in md.stats:
            if st.metadata_id == tf_op:
                path = (_text(st.str_value) if st.str_value
                        else stat_names.get(st.ref_value, ""))
                break
        out[e.key] = (trace_reduce.op_name(_text(md.name)),
                      innermost_scope(path) if path else None)
    return out


def _interval(line, ev) -> tuple[int, int]:
    """(start, end) in whole ns, as ``ProfileData`` gives them."""
    start = line.timestamp_ns + ev.offset_ps // 1000
    return start, start + ev.duration_ps // 1000


def scope_time(space, w0: float, w1: float) -> dict:
    """Program kind -> scope -> device seconds inside ``[w0, w1)`` (ns),
    mean over the chips.  An op belongs to the program whose run on the
    ``XLA Modules`` line holds its start; kinds are
    ``trace_reduce.PROGRAMS``' (any other program: ``other``)."""
    total = collections.defaultdict(lambda: collections.defaultdict(float))
    chips = 0
    for plane in space.planes:
        if not _text(plane.name).startswith("/device:TPU:"):
            continue
        chips += 1
        meta = _op_scopes(plane)
        lines = {_text(ln.name): ln for ln in plane.lines}
        runs = []                                   # (start, end, kind)
        mods = lines.get("XLA Modules")
        for ev in (mods.events if mods is not None else ()):
            s, e = _interval(mods, ev)
            name = meta.get(ev.metadata_id, ("", None))[0]
            kind = next((k for k, p in trace_reduce.PROGRAMS.items()
                         if name.startswith(p)), "other")
            runs.append((s, e, kind))
        runs.sort()
        starts = [r[0] for r in runs]
        ops = lines.get("XLA Ops")
        for ev in (ops.events if ops is not None else ()):
            name, scope = meta.get(ev.metadata_id, ("", None))
            if scope is None or name in trace_reduce.CONTAINERS:
                continue
            s0, e0 = _interval(ops, ev)
            s, e = max(s0, w0), min(e0, w1)
            if e <= s:
                continue
            i = bisect.bisect_right(starts, s0) - 1
            kind = runs[i][2] if i >= 0 and s0 < runs[i][1] else "other"
            total[kind][scope] += (e - s) * 1e-9
    return {k: {s: v / chips for s, v in d.items()} for k, d in total.items()}


# --------------------------------------------------------------------------
# Host spans and idle gaps
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    args: dict


def host_spans(data, prefix: str = SPAN_PREFIX) -> list[Span]:
    """Host events of a ``ProfileData`` whose name starts with ``prefix``,
    with the arguments they were opened with, in start order."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    args = {k: v for k, v in ev.stats if not k.startswith("_")}
                    out.append(Span(ev.name, ev.start_ns, ev.end_ns, args))
    out.sort(key=lambda sp: sp.start_ns)
    return out


def _gap_mids(data, w0: float, w1: float) -> list:
    """(seconds, middle) of each idle gap, in the order
    ``trace_reduce.reduce`` lists its gaps."""
    gaps = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                    if e > s:
                        ops.append((s, e))
        merged = trace_reduce._union(ops)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e - s >= trace_reduce.MIN_GAP_NS:
                gaps.append(((e - s) * 1e-9, (s + e) / 2))
    gaps.sort(key=lambda g: -g[0])
    return gaps


def label_gaps(base_gaps: list, mids: list, spans: list[Span]) -> list:
    """Each gap labelled by the innermost span enclosing its middle (the
    latest to start), else by its base label."""
    out = []
    for (seconds, label), (_, mid) in zip(base_gaps, mids):
        inside = [sp for sp in spans if sp.start_ns <= mid < sp.end_ns]
        if inside:
            label = max(inside, key=lambda sp: sp.start_ns).name
        out.append((seconds, label))
    return out


# --------------------------------------------------------------------------
# The summary
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ScopedSummary(trace_reduce.Summary):
    scope_time: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)  # [Span], window

    def scope_seconds(self, scope: str, kinds=("admit", "window")) -> float:
        return sum(self.scope_time.get(k, {}).get(scope, 0.0) for k in kinds)

    def span_args(self, name: str) -> list[dict]:
        return [sp.args for sp in self.spans if sp.name == name]

    def breakdown(self) -> dict:
        out = super().breakdown()
        top = sorted(((f"{kind}/{scope}", t)
                      for kind, d in self.scope_time.items()
                      for scope, t in d.items()), key=lambda kv: -kv[1])
        if top:
            out["device_scopes"] = [[n, t] for n, t in top[:10]]
        return out


def reduce(raw: bytes, host_span=None) -> ScopedSummary:
    """Scoped summary of a serialized XSpace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(raw)
    base = trace_reduce.reduce(data, host_span)
    steps = host_spans(data, "bench.step")
    w0 = min(sp.start_ns for sp in steps)
    w1 = max(sp.end_ns for sp in steps)
    spans = [sp for sp in host_spans(data)
             if w0 <= sp.start_ns and sp.end_ns <= w1]
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(trace_reduce.Summary)}
    fields["gaps"] = label_gaps(base.gaps, _gap_mids(data, w0, w1), spans)
    return ScopedSummary(**fields,
                         scope_time=scope_time(parse_xspace(raw), w0, w1),
                         spans=spans)


def reduce_dir(logdir: str, host_span=None) -> ScopedSummary:
    with open(trace_reduce.find_xplane(logdir), "rb") as f:
        return reduce(f.read(), host_span)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("logdir")
    s = reduce_dir(ap.parse_args().logdir)
    counts = collections.Counter(sp.name for sp in s.spans)
    print(json.dumps({"breakdown": s.breakdown(), "scope_time": s.scope_time,
                      "spans": dict(counts)}, indent=1))
