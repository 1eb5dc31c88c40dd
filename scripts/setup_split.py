"""Where a benchmark run's set-up time goes, split by phase.

    python scripts/setup_split.py --label <name> -- \\
        --workload <cell> --seed <n> --seconds <s> --trace 0

Runs ``bench/run.py`` with the arguments after ``--``, in this process,
and splits its set-up (process start to the opening of the measured
window) into parts:

- ``weights``: drawing the weights on the device (``bench.weights.draw``,
  waited for);
- ``engine``: building the ``ServeEngine``;
- for each program the warm-up dispatches (every admit bucket and the
  decode window): its trace, its lowering to MLIR, its compilation or its
  load from JAX's persistent cache (``hit`` or ``miss``, read from the
  cache's own log lines), and the rest of its first dispatch, waited for
  (execution and host work);
- ``later_dispatches``: the warm-up's dispatches of programs already
  loaded;
- ``rest``: everything else (imports, sessions, the schedule, host work
  between steps).

The JSON split goes to ``--out`` (default ``bench_out/setup_split/``,
one file per label and seed) and is printed as the last line, after the
benchmark's own result line.  Timing the phases waits for the weights and
for each warm-up dispatch, which the warm-up does anyway at each step's
end, so the set-up it reports is the benchmark's within noise.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import re  # noqa: E402
import runpy  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: JAX's events for the three stages of making a program, by part name.
STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
          "/jax/core/compile/backend_compile_duration": "compile_or_load_s"}
CACHE_LINE = re.compile(r"(Persistent compilation cache hit|PERSISTENT "
                        r"COMPILATION CACHE MISS) for '([^']+)'")


class Recorder(logging.Handler):
    """JAX's compile events, cache log lines and the timed phases, on the
    ``time.perf_counter`` clock, until the window opens."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.on = True
        self.events: list[dict] = []      # stage events (name, s, t_end)
        self.cache: list[dict] = []       # module, hit, t
        self.phases: dict[str, float] = {}
        self.dispatches: list[dict] = []  # kind, bucket, t0, t1
        self.t_window = None

    def on_duration(self, event, duration, **kw):
        if self.on and event in STAGES:
            self.events.append({"part": STAGES[event], "s": float(duration),
                                "name": str(kw.get("fun_name", "")),
                                "t": time.perf_counter()})

    def emit(self, record):
        m = CACHE_LINE.search(record.getMessage())
        if self.on and m:
            self.cache.append({"module": m.group(2),
                               "hit": m.group(1).startswith("Persistent"),
                               "t": time.perf_counter()})


def _install(rec: Recorder):
    import jax
    import jax.monitoring

    from bench import harness, weights
    from repro.serve.engine import ServeEngine

    jax.monitoring.register_event_duration_secs_listener(rec.on_duration)
    log = logging.getLogger("jax._src.compiler")
    log.addHandler(rec)
    log.setLevel(logging.DEBUG)
    log.propagate = False

    def timed_phase(name, fn):
        def run(*a, **kw):
            t0 = time.perf_counter()
            out = fn(*a, **kw)
            jax.block_until_ready(out)
            rec.phases[name] = rec.phases.get(name, 0.0) + (
                time.perf_counter() - t0)
            return out
        return run

    weights.draw = timed_phase("weights_s", weights.draw)
    ServeEngine.__init__ = timed_phase("engine_s", ServeEngine.__init__)
    dispatch = ServeEngine._dispatch

    def timed_dispatch(self, kind, fn, args, **kw):
        if not rec.on:
            return dispatch(self, kind, fn, args, **kw)
        t0 = time.perf_counter()
        out = dispatch(self, kind, fn, args, **kw)
        jax.block_until_ready(out)
        rec.dispatches.append({
            "kind": kind, "t0": t0, "t1": time.perf_counter(),
            "fun": getattr(fn, "__name__", kind),
            "bucket": int(args[2].shape[1]) if kind == "admit" else None})
        return out

    ServeEngine._dispatch = timed_dispatch
    measure = harness.measure

    def measure_then_stop(prep, sched, seconds, **kw):
        t_open = kw.get("t_open")

        def opened(t):
            rec.on = False
            rec.t_window = t
            if t_open is not None:
                t_open(t)
        kw["t_open"] = opened
        return measure(prep, sched, seconds, **kw)

    harness.measure = measure_then_stop


def split(rec: Recorder) -> dict:
    """The parts of the set-up, each in seconds, summing to the whole."""
    setup = rec.t_window - T_PROCESS
    programs, seen = [], set()
    later = 0.0
    for d in rec.dispatches:
        key = (d["kind"], d["bucket"])
        # The program's own events: those of jits traced inside it are
        # already within its trace.
        inside = [e for e in rec.events if d["t0"] <= e["t"] <= d["t1"]
                  and e["name"] in (d["fun"], f"jit_{d['fun']}",
                                    f"jit({d['fun']})")]
        if key in seen and not inside:
            later += d["t1"] - d["t0"]
            continue
        seen.add(key)
        row = {"program": d["kind"] if d["bucket"] is None
               else f"admit{d['bucket']}"}
        for part in STAGES.values():
            row[part] = sum(e["s"] for e in inside if e["part"] == part)
        row["cache"] = ["hit" if c["hit"] else "miss" for c in rec.cache
                        if d["t0"] <= c["t"] <= d["t1"]]
        row["first_dispatch_rest_s"] = (d["t1"] - d["t0"]) - sum(
            row[p] for p in STAGES.values())
        programs.append(row)
    named = (sum(rec.phases.values()) + later
             + sum(r["first_dispatch_rest_s"] + sum(r[p] for p in
                                                    STAGES.values())
                   for r in programs))
    totals = {p: sum(r[p] for r in programs) for p in STAGES.values()}
    totals["first_dispatch_rest_s"] = sum(r["first_dispatch_rest_s"]
                                          for r in programs)
    return {"setup_from_process_start_s": setup, **rec.phases,
            "programs": programs, "program_totals": totals,
            "later_dispatches_s": later, "rest_s": setup - named,
            "cache_hits": sum(c["hit"] for c in rec.cache),
            "cache_misses": sum(not c["hit"] for c in rec.cache)}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--" not in argv:
        raise SystemExit(__doc__.split("\n\n")[1])
    cut = argv.index("--")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--label", required=True)
    ap.add_argument("--out", default=str(ROOT / "bench_out" / "setup_split"))
    args = ap.parse_args(argv[:cut])
    bench_args = argv[cut + 1:]

    rec = Recorder()
    _install(rec)
    sys.argv = [str(ROOT / "bench" / "run.py"), *bench_args]
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
        rc = 0
    except SystemExit as e:
        rc = e.code or 0
    if rec.t_window is None:
        return rc or 1
    seed = bench_args[bench_args.index("--seed") + 1]
    out = {"label": args.label, "args": bench_args, **split(rec)}
    Path(args.out).mkdir(parents=True, exist_ok=True)
    Path(args.out, f"{args.label}_{seed}.json").write_text(
        json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
