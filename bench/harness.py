"""Serving benchmark harness: one run of one cell.

Everything a cell needs is found by name: the workload entry in
``BENCHMARK.json``, its mix in ``bench/traffic/<workload>.json``, its
configuration in ``bench/configs/<config>.json`` (with the plain reference
the file names beside it), and one reader per metric in
``bench/metrics/<metric>.py``.  A new cell, configuration or metric is new
files and new ``BENCHMARK.json`` entries; this module does not change.

A run: weights from the seed on the device; a warm-up that compiles every
admit bucket the mix can produce and the decode window; then an open loop
over the program's own ``ServeSession.step`` for the measured window, a
drain, the correctness comparison against the reference, and one JSON
line.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import check, traffic

ROOT = Path(__file__).resolve().parents[1]

#: Seconds after the window closes that requests due in it may take to
#: finish; one still unfinished then counts in ``failed``.
DRAIN_CAP_S = 60.0
#: The traced stretch of a ``--trace 1`` run: the last ``TRACE_S`` seconds
#: of the window (at most 40% of it), and on until an admitting step is in.
TRACE_S = 4.0
#: JAX's monitoring events that mean "a program was traced or compiled".
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# --------------------------------------------------------------------------
# Discovery by name
# --------------------------------------------------------------------------

def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A workload with everything found for it."""

    name: str
    entry: dict            # the BENCHMARK.json workload entry
    mix: dict              # bench/traffic/<name>.json
    config: dict           # bench/configs/<config>.json
    root: Path

    @property
    def serving(self) -> dict:
        return self.mix["serving"]

    def reference(self):
        return load_module(self.root / "bench" / "configs"
                           / self.config["reference"])

    def metrics(self, bench: dict, trace: bool) -> list[tuple[dict, object]]:
        """(entry, reader) of each metric this cell reports in a run with
        (``trace``) or without the profiler."""
        out = []
        for entry in bench["per_layer" if trace else "end_to_end"]:
            wl = entry.get("workloads")
            if wl is not None and self.name not in wl:
                continue
            out.append((entry, load_module(reader_path(self.root,
                                                       entry["name"]))))
        return out


def reader_path(root: Path, name: str) -> Path:
    """``bench/metrics/<name>.py``; a quantity split by the end-to-end
    metric it moves (``step_mfu.batch``) may share the reader of the name
    before its suffix (``step_mfu.py``)."""
    stem = name
    while True:
        path = root / "bench" / "metrics" / f"{stem}.py"
        if path.exists() or "." not in stem:
            return path
        stem = stem.rsplit(".", 1)[0]


def find_cell(root: Path, name: str) -> tuple[dict, Cell]:
    bench = _json(root / "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[0]
    mix = _json(root / "bench" / "traffic" / f"{name}.json")
    if (mix["config"], mix["traffic"]) != (entry["config"], entry["traffic"]):
        raise ValueError(f"{name}: traffic file names {mix['config']}/"
                         f"{mix['traffic']}, BENCHMARK.json "
                         f"{entry['config']}/{entry['traffic']}")
    config = _json(root / "bench" / "configs" / f"{entry['config']}.json")
    return bench, Cell(name, entry, mix, config, root)


def peaks_for(root: Path, device_kind: str) -> dict:
    table = _json(root / "bench" / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def arch_config(config: dict):
    """The program's configuration object, as the file states it."""
    from repro.configs.registry import get_config

    return dataclasses.replace(get_config(config["registry"]),
                               **config["model"])


def sizes_of(cfg) -> dict:
    return dataclasses.asdict(cfg)


# --------------------------------------------------------------------------
# Device
# --------------------------------------------------------------------------

def device_info(chips: int, *, require_chip: bool = True) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and (info["platform"] == "cpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} accelerator chip(s); JAX found "
                     f"{len(devs)} {info['platform']} device(s)")
    return info


def peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program kept.  The environment's ``JAX_COMPILATION_CACHE_DIR``
    and ``JAX_COMPILATION_CACHE_MAX_SIZE`` are overridden: a cache outside
    the checkout could be shared with another checkout, and a size cap
    evicts programs a later run then compiles inside its set-up."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX traces and compilations while ``armed``."""

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1

    def close(self):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)


# --------------------------------------------------------------------------
# The open loop
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """Host-clock record of one run (seconds on ``time.perf_counter``)."""

    t0: float                       # window opens
    t_close: float                  # end of the last step begun in the window
    due: np.ndarray                 # (N,) absolute due times
    handed: np.ndarray              # (N,) when handed to the engine (nan: never)
    admit_start: np.ndarray         # (N,) start of the step that admitted it
    t_first: np.ndarray             # (N,) first token seen (nan: none)
    t_last: np.ndarray              # (N,) last token seen
    budget: np.ndarray              # (N,) tokens asked
    prompt_len: np.ndarray          # (N,)
    outcome: list                   # (N,) engine outcome or None
    outputs: list                   # (N,) served token arrays
    prompts: list                   # (N,) prompt token arrays
    steps: list                     # dicts: t_start, t_end, prompts, positions
    tokens_in_window: int = 0
    wake_late_max_s: float = 0.0
    traced: tuple[float, float] | None = None   # host span of traced steps
    pauses: list = dataclasses.field(default_factory=list)  # the profiler's

    @property
    def window_s(self) -> float:
        return self.t_close - self.t0

    def attempted(self) -> np.ndarray:
        return np.nonzero(~np.isnan(self.handed))[0]

    def finished(self) -> np.ndarray:
        return np.array([i for i in self.attempted()
                         if self.outcome[i] in ("ok", "eos")], int)


class Tracer:
    """Profiler over the end of the window (``--trace 1``): it starts at
    ``start_at`` and stops at the first step boundary at or after the
    window's close whose stretch holds an admitting step (so every
    program kind is in it).  Starting and stopping block the host (the
    stop writes the trace, which takes seconds), so the stop falls after
    the close, where no step of the window waits behind it; both pauses
    are kept in ``pauses`` and host-clock latencies leave them out."""

    def __init__(self, logdir: str | None, start_at: float,
                 clock=time.perf_counter):
        self.logdir, self.start_at, self.clock = logdir, start_at, clock
        self.on = False
        self.span: tuple[float, float] | None = None
        self.pauses: list[tuple[float, float]] = []
        self._first = None
        self._admitted = False

    def before_step(self, now: float):
        if (self.logdir is None or self.on or self.span is not None
                or now < self.start_at):
            return
        import jax

        # Host spans and device ops only: Python function tracing would
        # slow the host loop that is being measured.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        p0 = self.clock()
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self.pauses.append((p0, self.clock()))
        self.on = True

    def after_step(self, t_start: float, t_end: float, *,
                   admitted: bool = False, closed: bool = False):
        if not self.on:
            return
        if self._first is None:
            self._first = t_start
        self._admitted |= admitted
        if closed and self._admitted:
            self.stop(t_end)

    def stop(self, t_end: float):
        """Stop the profiler if it is on (the end of the drain stops it
        whether or not an admitting step came)."""
        if not self.on:
            return
        import jax

        p0 = self.clock()
        jax.profiler.stop_trace()
        self.pauses.append((p0, self.clock()))
        self.on = False
        self.span = (self._first, t_end)


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def open_loop(sess, sched: traffic.Schedule, seconds: float, *,
              saturated: bool, slots: int, tracer: Tracer,
              counter: CompileCounter, clock=time.perf_counter) -> Served:
    """Drive ``sess`` for ``seconds``: every request whose due time has
    passed is handed over (a saturated mix keeps ``slots`` waiting), then
    one ``step()``; idle, the loop sleeps until the next due time.  Then
    the requests handed over drain, for at most ``DRAIN_CAP_S``."""
    n = len(sched)
    nan = lambda: np.full(n, np.nan)  # noqa: E731
    rec = Served(t0=0.0, t_close=0.0, due=nan(), handed=nan(),
                 admit_start=nan(), t_first=nan(), t_last=nan(),
                 budget=sched.output_lens.copy(),
                 prompt_len=sched.prompt_lens.copy(), outcome=[None] * n,
                 outputs=[None] * n, prompts=sched.prompts, steps=[])
    seen = np.zeros(n, int)
    nxt = 0

    def hand(now, t_stop):
        nonlocal nxt
        with _annotate("bench.enqueue"):
            while nxt < n and rec.due[nxt] <= now and rec.due[nxt] < t_stop:
                if saturated and sess.queue_depth() >= 2 * slots:
                    break
                sess.enqueue(nxt)
                rec.handed[nxt] = now
                nxt += 1

    def step(in_window: bool):
        tracer.before_step(clock())
        ts = clock()
        with _annotate("bench.step"):
            sess.step()
        te = clock()
        with _annotate("bench.collect"):
            cand = {ri for ri in sess.slot_req if ri >= 0}
            cand.update(sess.drain_done())
            prompts, positions = [], []
            for ri in cand:
                out = sess.outputs[ri]
                k = len(out)
                if k > seen[ri]:
                    if seen[ri] == 0:
                        rec.t_first[ri] = te
                        rec.admit_start[ri] = ts
                        prompts.append(int(rec.prompt_len[ri]))
                    positions += [int(rec.prompt_len[ri]) + j - 1
                                  for j in range(max(seen[ri], 1), k)]
                    rec.t_last[ri] = te
                    if in_window:
                        rec.tokens_in_window += k - seen[ri]
                    seen[ri] = k
                if sess.outcomes[ri] is not None:
                    rec.outcome[ri] = sess.outcomes[ri]
        rec.steps.append({"t_start": ts, "t_end": te, "prompts": prompts,
                          "positions": positions})
        return ts, te, bool(prompts)

    rec.t0 = t0 = clock()
    rec.due[:] = t0 + sched.due_s
    end = t0 + seconds
    counter.armed = True
    rec.t_close = t0
    while True:
        now = clock()
        if now >= end:
            break
        hand(now, np.inf)
        if not sess.busy:
            wake = min(rec.due[nxt] if nxt < n else end, end)
            time.sleep(max(0.0, wake - now))
            rec.wake_late_max_s = max(rec.wake_late_max_s, clock() - wake)
            continue
        ts, te, admitted = step(in_window=True)
        tracer.after_step(ts, te, admitted=admitted)
        rec.t_close = te
    rec.t_close = max(rec.t_close, end)
    counter.armed = False
    tracer.after_step(rec.t_close, rec.t_close, closed=True)
    # Drain: what was due in the window finishes; nothing new arrives.
    cap = rec.t_close + DRAIN_CAP_S
    while clock() < cap:
        if not saturated:
            hand(clock(), end)
        if not sess.busy:
            if saturated or nxt >= n or rec.due[nxt] >= end:
                break
            continue
        ts, te, admitted = step(in_window=False)
        tracer.after_step(ts, te, admitted=admitted, closed=True)
    tracer.stop(clock())
    rec.traced, rec.pauses = tracer.span, tracer.pauses
    for ri in range(n):
        if not np.isnan(rec.handed[ri]):
            rec.outputs[ri] = np.asarray(sess.outputs[ri], np.int32)
            rec.outcome[ri] = sess.outcomes[ri]
    return rec


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: Cell
    served: Served
    sizes: dict
    peaks: dict
    setup_s: float
    trace: object = None            # trace_reduce.Summary, --trace 1 only


def _phantom(mix: dict):
    """A request that is never handed over: it pins the session's shapes
    (its slot count and insert window) to what the mix can produce at
    most, whatever the seed drew."""
    from repro.serve.engine import Request

    return Request(tokens=np.zeros(traffic.bucket_bounds(mix["prompt_tokens"])[1],
                                   np.int32),
                   max_new_tokens=traffic.bucket_bounds(mix["output_tokens"])[1])


def session_requests(requests: list, mix: dict, slots: int) -> list:
    """``requests`` plus phantoms: at least one, and enough to fill
    ``slots``."""
    return list(requests) + [_phantom(mix)] * max(1, slots - len(requests))


@dataclasses.dataclass
class Prepared:
    """Weights on the device and a warm engine for one cell."""

    cell: Cell
    cfg: object
    sizes: dict
    params: object
    eng: object
    insert_window: int

    @property
    def slots(self) -> int:
        return int(self.cell.serving["slots"])


def jax_seed(seed: int) -> int:
    """A 31-bit key seed derived from a seed of any size."""
    return int(np.random.default_rng([seed, 7]).integers(0, 2**31 - 1))


def prepare(cell: Cell, seed: int, engine=None) -> Prepared:
    """Draw the weights from ``seed`` and build and warm the engine: every
    admit bucket the mix can produce and the decode window run once, in a
    session shaped like the measured one.  With ``engine`` (already warm
    for this cell) only the weights are drawn anew."""
    from repro.model import model as M
    from repro.serve.engine import Request, ServeEngine, ServeSession

    from bench import weights

    cfg = arch_config(cell.config)
    sv = cell.serving
    if traffic.worst_total(cell.mix) > int(sv["max_len"]):
        raise ValueError(f"{cell.name}: max_len {sv['max_len']} is below "
                         f"the longest request the mix can produce")
    if engine is not None:
        engine.params = None
        gc.collect()
    params = weights.draw(M.abstract_params(cfg), jax_seed(seed))
    slots = int(sv["slots"])
    if engine is not None:
        engine.params = params
        iw = ServeSession(engine, session_requests([], cell.mix, slots),
                          slots=slots, external=True).insert_window
        return Prepared(cell, cfg, sizes_of(cfg), params, engine, iw)
    eng = ServeEngine(cfg, params, max_len=int(sv["max_len"]),
                      decode_window=int(sv["decode_window"]))
    warm = [Request(tokens=np.zeros(p, np.int32), max_new_tokens=2)
            for p in traffic.admit_buckets(cell.mix)]
    sess = ServeSession(eng, session_requests(warm, cell.mix, slots),
                        slots=slots, external=True)
    for i in range(len(warm)):
        sess.enqueue(i)
        while sess.busy:
            sess.step()
    sess.close()
    return Prepared(cell, cfg, sizes_of(cfg), params, eng, sess.insert_window)


def measure(prep: Prepared, sched: traffic.Schedule, seconds: float, *,
            trace_dir: str | None = None, t_open=None):
    """One measured window over ``sched`` in a fresh session; returns
    (Served, engine stats, compilations in the window, peak bytes).
    ``t_open`` is called just before the window opens."""
    from repro.serve.engine import Request, ServeSession

    requests = [Request(tokens=p, max_new_tokens=int(o))
                for p, o in zip(sched.prompts, sched.output_lens)]
    sess = ServeSession(prep.eng,
                        session_requests(requests, prep.cell.mix, prep.slots),
                        slots=prep.slots, external=True)
    if sess.insert_window != prep.insert_window:
        raise RuntimeError("warm-up and measured sessions differ in shape")
    counter = CompileCounter()
    t_window = time.perf_counter()
    if t_open is not None:
        t_open(t_window)
    tracer = Tracer(trace_dir, t_window + seconds - min(TRACE_S, 0.4 * seconds))
    saturated = prep.cell.mix["arrivals"]["process"] == "saturated"
    try:
        served = open_loop(sess, sched, seconds, saturated=saturated,
                           slots=prep.slots, tracer=tracer, counter=counter)
    finally:
        counter.close()
    mem = peak_bytes()
    stats = dict(sess.stats)
    sess.close()
    del sess
    gc.collect()
    return served, stats, counter.count, mem


def run(name: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, t_process: float | None = None,
        require_chip: bool = True, trace_dir: str | None = None,
        control: bool = False, prepared: Prepared | None = None,
        log=lambda msg: print(msg, file=sys.stderr, flush=True)) -> dict:
    """One run of workload ``name``; returns the result object.
    ``control`` puts the float8 control in the program's place: the
    control's tokens are compared instead of the served ones, and
    ``correct`` comes from the same limits (for ``control.py``; the
    benchmark's own runs never set it).  The program's own reading then
    goes under ``program_max_gap_sd``."""
    t_process = time.perf_counter() if t_process is None else t_process
    bench, cell = find_cell(root, name)
    enable_cache(root)
    dev = device_info(int(cell.entry["chips"]), require_chip=require_chip)
    peaks = peaks_for(root, dev["kind"]) if require_chip else {}
    prep = prepare(cell, seed, None if prepared is None else prepared.eng)
    sched = traffic.generate(cell.mix, seed, seconds, prep.cfg.vocab_size)
    tmp = None
    if trace:
        tmp = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
    opened = {}
    served, stats, compiles, mem = measure(
        prep, sched, seconds, trace_dir=tmp,
        t_open=lambda t: opened.setdefault("t", t))
    setup_s = opened["t"] - t_process

    late = served.handed - served.due
    late = late[~np.isnan(late)]
    log(f"{name}: seed {seed}, {len(sched)} requests scheduled, "
        f"{served.attempted().size} handed over; generator: hand-over after "
        f"due p50 {1e3 * float(np.median(late)) if late.size else 0:.3f} ms, "
        f"max {1e3 * float(late.max()) if late.size else 0:.3f} ms, idle "
        f"wake-up late by at most {1e3 * served.wake_late_max_s:.3f} ms")
    log(f"{name}: setup {setup_s:.3f} s, window {served.window_s:.3f} s, "
        f"{served.tokens_in_window} tokens in the window, {compiles} "
        f"compilations in the window, admissions {stats['admissions']}, "
        f"decode dispatches {stats['decode_dispatches']}, peak bytes {mem}")

    summary = None
    if trace:
        from bench import trace_reduce

        summary = trace_reduce.reduce_dir(tmp, served.traced)
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)

    ctx = Context(cell=cell, served=served, sizes=prep.sizes, peaks=peaks,
                  setup_s=setup_s, trace=summary)
    metrics = {}
    for entry, reader in cell.metrics(bench, trace):
        v = reader.read(ctx)
        if v is not None:
            metrics[entry["name"]] = {"value": float(v), "unit": entry["unit"]}

    checks, program_gap = correctness(cell, served, prep.params, prep.sizes,
                                      seed, log, control=control)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    attempted = served.attempted()
    failed = int(sum(served.outcome[i] not in ("ok", "eos") for i in attempted))
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": mem}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    result = {"correct": bool(correct), "attempted": int(attempted.size),
              "failed": failed, "metrics": metrics, "device": device,
              "compiles_in_window": compiles}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    if prepared is not None:
        prepared.eng = prep.eng
    if control:
        result["program_max_gap_sd"] = program_gap
    result["checks"] = checks
    return result


def correctness(cell: Cell, served: Served, params, sizes, seed, log, *,
                control: bool = False) -> tuple[dict, float | None]:
    """Each number compared, with its limit (see ``bench/check.py``), and
    the served tokens' widest gap.  With ``control`` the gap compared is
    that of the tokens the float8 control puts first."""
    ck = cell.mix["check"]
    fin = served.finished()
    short = int(sum(served.outputs[i].size != served.budget[i] for i in fin))
    rng = np.random.default_rng([seed, 1])
    total = lambda i: int(served.prompt_len[i] + served.budget[i])  # noqa: E731
    pick = check.pick_sample(list(fin), total, int(ck["requests"]), rng)
    seqs = [(served.prompts[i], served.outputs[i]) for i in pick]
    checks = {"short_outputs": {"value": short, "limit": 0},
              "sample_missing": {"value": int(not seqs), "limit": 0}}
    if not seqs:
        return checks, None
    t0 = time.perf_counter()
    got = check.compare(cell.reference(), params, sizes, seqs,
                        _pad_to(cell.mix), control=control)
    log(f"reference over {len(seqs)} requests, {got['tokens']} served "
        f"tokens, took {time.perf_counter() - t0:.3f} s")
    gap = got["control_max_gap_sd"] if control else got["max_gap_sd"]
    checks["max_gap_sd"] = {"value": gap, "limit": float(ck["max_gap_sd"])}
    return checks, got["max_gap_sd"]


def _pad_to(mix: dict) -> int:
    return -(-traffic.worst_total(mix) // 128) * 128
