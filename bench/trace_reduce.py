"""Reduce a profiler trace (``.xplane.pb``) to the per-layer numbers.

    python bench/trace_reduce.py <trace dir> --dump    # what a trace holds

Read with ``jax.profiler.ProfileData``.  On a TPU each chip is a plane
``/device:TPU:<n>`` whose ``XLA Modules`` line holds one event per program
run (named after its jit: ``jit_admit(...)``, ``jit_win(...)``) and whose
``XLA Ops`` line holds the operations inside them, Pallas kernels under
their kernel's name.  The host plane's threads carry the harness's spans
(``bench.enqueue``, ``bench.step``, ``bench.collect``).  All planes share
one clock, in nanoseconds from the start of the profile.

The traced window runs from the start of the first ``bench.step`` span to
the end of the last.  Busy time is the union of the device's op intervals
inside it, averaged over the chips; an idle gap is labelled by the
harness span that encloses its middle on the host.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import os
import re

#: Program kinds by the name of the jitted function the engine builds.
PROGRAMS = {"admit": "jit_admit", "window": "jit_win"}
#: Kernel families by the names their Pallas calls carry in the trace.
KERNELS = {"wkv": ("wkv_pallas", "wkv_decode_pallas", "wkv_decode_window_pallas")}
#: Ops that only contain others (their time is their children's).
CONTAINERS = ("while", "conditional", "call")
HOST_SPANS = ("bench.enqueue", "bench.step", "bench.collect")
#: Gaps shorter than this between device ops are rounding, not idling.
MIN_GAP_NS = 1000

_SHAPE = re.compile(r"\b(pred|[subf]\d+|bf16)\[([\d,]*)\]")
_SUFFIX = re.compile(r"\.\d+$")


def op_name(text: str) -> str:
    """Short name of an op event, whose name is the HLO instruction text:
    ``%wkv_pallas.5 = (...) custom-call(...)`` -> ``wkv_pallas``."""
    return _SUFFIX.sub("", text.split(" = ", 1)[0].lstrip("%"))


@dataclasses.dataclass
class KernelCall:
    name: str
    seconds: float
    operands: list          # [(dtype, dims)]
    results: list


def parse_shapes(text: str) -> tuple[list, list]:
    """(results, operands) of one HLO instruction's text:
    ``%x = (bf16[..], f32[..]) custom-call(bf16[..] %a, ...), ...``."""
    head, sep, rest = text.partition("custom-call(")
    if not sep:
        return [], []
    results = [(d, tuple(int(x) for x in s.split(",") if x))
               for d, s in _SHAPE.findall(head[head.find("=") + 1:])]
    operands = [(d, tuple(int(x) for x in s.split(",") if x))
                for d, s in _SHAPE.findall(rest.split("), custom_call_target", 1)[0])]
    return results, operands


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    programs: dict            # kind -> [seconds]
    op_time: dict             # short op name -> seconds (mean over chips)
    kernels: dict             # family -> [KernelCall]
    gaps: list                # [(seconds, label)] longest first
    host_span: tuple | None   # (t_first_step, t_last_step) host clock

    def program_times(self, kind: str) -> list[float]:
        return self.programs.get(kind, [])

    def kernel_calls(self, family: str) -> list[KernelCall]:
        return self.kernels.get(family, [])

    def steps_within(self, steps: list) -> list:
        """The harness's step records that the traced stretch covers."""
        if self.host_span is None:
            return []
        a, b = self.host_span
        return [s for s in steps if s["t_start"] >= a and s["t_end"] <= b]

    def breakdown(self) -> dict:
        top = sorted(self.op_time.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[label, s] for s, label in self.gaps[:10]]}


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def reduce(data, host_span=None) -> Summary:
    """Summary of a ``ProfileData``."""
    host = []                                     # (start, end, name) ns
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.start_ns, ev.end_ns, ev.name))
    steps = [(s, e) for s, e, n in host if n == "bench.step"]
    if not steps or not devices:
        raise ValueError("trace holds no bench.step span or no device plane")
    w0, w1 = min(s for s, _ in steps), max(e for _, e in steps)
    busy = 0.0
    programs = collections.defaultdict(list)
    op_time = collections.defaultdict(float)
    kernels = collections.defaultdict(list)
    gaps = []
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    if w0 <= ev.start_ns and ev.end_ns <= w1:
                        for kind, prefix in PROGRAMS.items():
                            if ev.name.startswith(prefix):
                                programs[kind].append(ev.duration_ns * 1e-9)
            elif line.name == "XLA Ops":
                for ev in line.events:
                    s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                    if e <= s:
                        continue
                    ops.append((s, e))
                    name = op_name(ev.name)
                    if name not in CONTAINERS:
                        op_time[name] += (e - s) * 1e-9
                    for fam, names in KERNELS.items():
                        if name in names:
                            res, opr = parse_shapes(ev.name)
                            kernels[fam].append(KernelCall(
                                name, ev.duration_ns * 1e-9, opr, res))
        merged = _union(ops)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e - s >= MIN_GAP_NS:
                mid = (s + e) / 2
                label = next((n for hs, he, n in host if hs <= mid < he
                              and n != "bench.step"), None)
                if label is None:
                    label = next((n for hs, he, n in host if hs <= mid < he),
                                 "outside harness spans")
                gaps.append(((e - s) * 1e-9, label))
    gaps.sort(key=lambda g: -g[0])
    n = len(devices)
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy / n,
                   programs=dict(programs),
                   op_time={k: v / n for k, v in op_time.items()},
                   kernels=dict(kernels), gaps=gaps, host_span=host_span)


def reduce_dir(logdir: str, host_span=None) -> Summary:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(find_xplane(logdir)), host_span)


def dump(logdir: str, events: int = 40) -> None:
    """Print what a trace holds: planes, lines, event names and stats."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(logdir))
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events; top names "
                  f"{names.most_common(8)}")
            shown = set()
            for ev in evs:
                if ev.name in shown or len(shown) >= events:
                    continue
                shown.add(ev.name)
                print(f"    {ev.name!r} start {ev.start_ns} dur "
                      f"{ev.duration_ns} stats {_stats(ev)}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("logdir")
    ap.add_argument("--dump", action="store_true")
    a = ap.parse_args()
    if a.dump:
        dump(a.logdir)
    else:
        print(reduce_dir(a.logdir).breakdown())
