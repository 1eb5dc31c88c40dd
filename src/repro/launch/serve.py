"""Serving launcher: batched greedy generation with the ServeEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
      --batch 4 --prompt-len 16 --new-tokens 32

``--continuous`` runs the continuous-batching scheduler instead: a ragged
request queue (prompt lengths and budgets drawn per request) served
through a fixed slot pool with EOS/budget detection inside the jitted
window and slot recycling:

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --smoke \
      --continuous --requests 8 --slots 2 --temperature 0.8 --top-k 40

Fault-isolation knobs (all ``--continuous``): ``--deadline-ms`` /
``--max-queue`` bound request latency and queue depth (typed ``deadline``
/ ``shed`` outcomes), ``--watchdog-timeout`` arms the per-dispatch hang
watchdog, ``--snapshot-every`` / ``--snapshot-dir`` checkpoint the engine
for preemption recovery, and ``--chaos-seed`` (+ ``--chaos-nan-rate``
etc.) runs the serve under seed-deterministic fault injection — the
chaos-smoke drill asserts every injected fault was quarantined and
recovered with all requests still completing:

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --smoke \
      --continuous --requests 6 --slots 2 --chaos-seed 7 --chaos-nan-at 2

``--paged`` swaps the per-slot dense KV rings for a pooled page store
(``--page-size`` / ``--pool-pages``) and runs the paged drill: the same
requests served by a dense reference engine, exiting nonzero unless
every stream is bit-identical and the page-table audit is clean.
``--prefix-len N`` additionally registers one N-token shared prefix and
admits every request through it (recurrent-state prefix sharing):

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
      --continuous --paged --requests 5 --slots 2 --max-len 128 \
      --prefix-len 40 --pool-pages 4

``--replicas N`` (with ``--continuous``) serves the same queue through a
health-checked replica fleet (:mod:`repro.serve.fleet`) instead of one
engine, and runs the fleet drill: ``--chaos-replica-kill-at K`` kills
one replica at its K-th decode dispatch (``--chaos-bitflip-at`` flips a
state bit for the ``--checksum-every`` corruption detector), and the
drill exits nonzero unless every request completes on the survivors
with outcome ok/eos/recovered and every stream is bit-identical to a
fault-free single-engine run:

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --smoke \
      --continuous --replicas 3 --requests 6 --slots 2 \
      --snapshot-every 1 --checksum-every 2 --chaos-replica-kill-at 2
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.model import model as M
from repro.serve.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--decode-window", type=int, default=8,
                    help="tokens generated per decode dispatch (K)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching scheduler (ragged queue, "
                         "slot recycling) instead of lockstep generate()")
    ap.add_argument("--requests", type=int, default=8,
                    help="[--continuous] queued requests")
    ap.add_argument("--slots", type=int, default=4,
                    help="[--continuous] batch slots")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="[--continuous] 0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="[--continuous] wall-clock budget per request; "
                         "expired requests end with outcome 'deadline'")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="[--continuous] bounded admission queue beyond "
                         "the slot pool; overflow is shed, not queued")
    ap.add_argument("--watchdog-timeout", type=float, default=None,
                    help="[--continuous] per-dispatch hang deadline (s)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="[--continuous] snapshot the engine every N "
                         "decode dispatches (needs --snapshot-dir)")
    ap.add_argument("--snapshot-dir", default=None)
    ap.add_argument("--restore-from", default=None,
                    help="[--continuous] resume a snapshotted serve "
                         "(same requests/args/seed)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="[--continuous] enable seed-deterministic fault "
                         "injection (chaos drill mode: exits nonzero "
                         "unless every fault is recovered)")
    ap.add_argument("--chaos-nan-rate", type=float, default=0.0)
    ap.add_argument("--chaos-drop-rate", type=float, default=0.0)
    ap.add_argument("--chaos-hang-rate", type=float, default=0.0)
    ap.add_argument("--chaos-nan-at", type=int, nargs="*", default=(),
                    help="pin NaN faults to decode-dispatch indices")
    ap.add_argument("--chaos-drop-at", type=int, nargs="*", default=())
    ap.add_argument("--chaos-hang-at", type=int, nargs="*", default=())
    ap.add_argument("--paged", action="store_true",
                    help="[--continuous] pooled KV pages + per-slot page "
                         "tables (drill mode: exits nonzero unless every "
                         "stream is bit-identical to the dense engine)")
    ap.add_argument("--page-size", type=int, default=32,
                    help="[--paged] tokens per KV page (multiple of 32)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="[--paged] private pages per node pool "
                         "(default: dense-equivalent sizing)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="[--paged] register one shared prefix of this "
                         "many tokens and admit every request through it")
    ap.add_argument("--replicas", type=int, default=1,
                    help="[--continuous] serve through a replica fleet "
                         "(drill mode: exits nonzero unless all requests "
                         "complete bit-identically on survivors)")
    ap.add_argument("--checksum-every", type=int, default=0,
                    help="[--continuous] arm silent-corruption checksums; "
                         "shadow spot check every N windows")
    ap.add_argument("--chaos-bitflip-at", type=int, nargs="*", default=(),
                    help="pin silent state bit flips to decode-dispatch "
                         "indices (needs --checksum-every to detect)")
    ap.add_argument("--chaos-replica-kill-at", type=int, nargs="*",
                    default=(),
                    help="[--replicas] kill one replica at these decode-"
                         "dispatch indices (fires once; needs "
                         "--snapshot-every for handoff)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if cfg.is_enc_dec:
        raise SystemExit("enc-dec serving demo: use examples/serve_decode.py")

    if args.paged and not args.continuous:
        raise SystemExit("--paged requires --continuous")
    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    if args.replicas > 1 and not args.continuous:
        raise SystemExit("--replicas requires --continuous")
    if args.chaos_bitflip_at and not args.checksum_every:
        raise SystemExit("--chaos-bitflip-at needs --checksum-every to "
                         "be detectable")

    print(f"initializing {cfg.name} ({cfg.param_count()/1e6:.1f}M params)...")
    params = M.init_params(cfg, jax.random.key(args.seed))
    engine = ServeEngine(cfg, params, max_len=args.max_len,
                         decode_window=args.decode_window,
                         paged=args.paged, page_size=args.page_size,
                         pool_pages=args.pool_pages)
    rng = np.random.default_rng(args.seed)

    if args.continuous:
        if args.prompt_len < 1 or args.new_tokens < 1 or args.requests < 1:
            raise SystemExit(
                "--continuous needs --prompt-len, --new-tokens and "
                "--requests all >= 1")
        # Ragged draws in [lo, arg]: lo collapses to the arg itself when
        # the arg is small, so tiny smoke settings stay valid.
        p_lo = min(4, args.prompt_len)
        n_lo = min(2, args.new_tokens)
        reqs = [
            Request(
                tokens=jnp.asarray(
                    rng.integers(
                        0, cfg.vocab_size,
                        (int(rng.integers(p_lo, args.prompt_len + 1)),)),
                    jnp.int32),
                max_new_tokens=int(rng.integers(n_lo, args.new_tokens + 1)),
            )
            for _ in range(args.requests)
        ]
        if args.paged and args.prefix_len:
            if args.prefix_len < args.page_size:
                raise SystemExit("--prefix-len must cover at least one page")
            prefix = rng.integers(
                0, cfg.vocab_size, (args.prefix_len,)).astype(np.int32)
            pid = engine.register_prefix(prefix)
            reqs = [
                Request(tokens=np.concatenate(
                            [prefix, np.asarray(r.tokens, np.int32)]),
                        max_new_tokens=r.max_new_tokens, prefix_id=pid)
                for r in reqs
            ]
        if args.replicas > 1:
            return _fleet_drill(args, cfg, params, reqs)
        paged_ref = None
        if args.paged:
            # Dense reference on the same weights/requests: the paged
            # drill's bit-identity oracle (prefix admissions included —
            # the dense engine just re-prefills the prefix per request).
            dense_eng = ServeEngine(cfg, params, max_len=args.max_len,
                                    decode_window=args.decode_window)
            paged_ref = dense_eng.serve(
                [Request(tokens=r.tokens, max_new_tokens=r.max_new_tokens)
                 for r in reqs],
                slots=args.slots, temperature=args.temperature,
                top_k=args.top_k, eos_id=args.eos_id, seed=args.seed)
        useful = sum(r.max_new_tokens for r in reqs)
        chaos = baseline = None
        if args.chaos_seed is not None:
            from repro.serve.chaos import ChaosInjector

            chaos = ChaosInjector(
                seed=args.chaos_seed, nan_rate=args.chaos_nan_rate,
                drop_rate=args.chaos_drop_rate,
                hang_rate=args.chaos_hang_rate,
                nan_at=tuple(args.chaos_nan_at),
                drop_at=tuple(args.chaos_drop_at),
                hang_at=tuple(args.chaos_hang_at),
                bitflip_at=tuple(args.chaos_bitflip_at),
            )
            # Fault-free reference for the isolation invariant: every
            # request's stream under chaos must match this bit-for-bit.
            baseline = engine.serve(
                reqs, slots=args.slots, temperature=args.temperature,
                top_k=args.top_k, eos_id=args.eos_id, seed=args.seed)
        t0 = time.perf_counter()
        outs = engine.serve(reqs, slots=args.slots,
                            temperature=args.temperature, top_k=args.top_k,
                            eos_id=args.eos_id, seed=args.seed,
                            deadline_ms=args.deadline_ms,
                            max_queue=args.max_queue,
                            watchdog_timeout_s=args.watchdog_timeout,
                            snapshot_every=args.snapshot_every,
                            snapshot_dir=args.snapshot_dir,
                            restore_from=args.restore_from, chaos=chaos,
                            checksum_every=args.checksum_every)
        dt = time.perf_counter() - t0
        emitted = sum(o.size for o in outs)
        st = engine.last_serve_stats
        print(f"served {len(reqs)} ragged requests "
              f"({emitted}/{useful} tokens) in {dt:.2f}s "
              f"({emitted/dt:.1f} tok/s; {st['decode_dispatches']} decode "
              f"dispatches, {st['admissions']} admissions, "
              f"{st['slot_steps']} slot-steps at K={args.decode_window})")
        counts: dict[str, int] = {}
        for o in outs:
            counts[o.outcome] = counts.get(o.outcome, 0) + 1
        print("outcomes:", " ".join(
            f"{k}={v}" for k, v in sorted(counts.items())))
        lens = [int(o.size) for o in outs]
        print(f"per-request emitted lengths: {lens}")
        print("first request tokens:", outs[0].tolist())
        if chaos is not None:
            faults = sum(chaos.counters.values())
            print(f"chaos drill: {faults} injected faults "
                  f"{dict(chaos.counters)}; quarantines="
                  f"{st['quarantines']} recoveries={st['recoveries']} "
                  f"retries={st['dispatch_retries']} "
                  f"watchdog_timeouts={st['watchdog_timeouts']}")
            if faults == 0:
                raise SystemExit("chaos drill injected no faults — "
                                 "pin some with --chaos-nan-at etc.")
            if chaos.counters["nan"] and not st["recoveries"]:
                raise SystemExit("chaos drill: NaN faults injected but "
                                 "none recovered")
            if chaos.counters["bitflip"] and not st["corruptions"]:
                raise SystemExit("chaos drill: bit flips injected but the "
                                 "checksum chain detected none")
            bad = [r for r in outs
                   if r.outcome not in ("ok", "eos", "recovered")]
            if bad:
                raise SystemExit(
                    f"chaos drill: unrecovered outcomes {bad}")
            for i, (want, got) in enumerate(zip(baseline, outs)):
                if not np.array_equal(np.asarray(want), np.asarray(got)):
                    raise SystemExit(
                        f"chaos drill: request {i} diverged from the "
                        "fault-free run — isolation invariant broken")
            print("chaos drill: all faults recovered; every stream "
                  "bit-identical to the fault-free run")
        if args.paged:
            pg = engine.last_paged_stats
            print(f"paged: page_size={pg['page_size']} "
                  f"shared_pages={pg['shared_pages']} "
                  f"pool_bytes={pg['pool_bytes']} "
                  f"dense_bytes={pg['dense_bytes']} "
                  f"peak_mapped_bytes={pg['peak_mapped_bytes']} "
                  f"prefix_admissions={st['prefix_admissions']} "
                  f"page_waits={st['page_waits']}")
            if pg["page_table_violations"]:
                raise SystemExit(
                    f"paged drill: {pg['page_table_violations']} page-"
                    "table violations (double-map / freed-page reach)")
            if args.pool_pages is not None and (
                    pg["pool_bytes"] >= pg["dense_bytes"]):
                raise SystemExit(
                    "paged drill: explicitly sized pool does not beat the "
                    f"dense footprint ({pg['pool_bytes']} >= "
                    f"{pg['dense_bytes']} bytes)")
            for i, (want, got) in enumerate(zip(paged_ref, outs)):
                if want.outcome != got.outcome or not np.array_equal(
                        np.asarray(want), np.asarray(got)):
                    raise SystemExit(
                        f"paged drill: request {i} diverged from the dense "
                        f"engine ({want.outcome} vs {got.outcome}) — paging "
                        "must be an exact storage-layout change")
            print("paged drill: every stream bit-identical to the dense "
                  "engine")
        return

    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
    )
    t0 = time.perf_counter()
    out = engine.generate(prompts, args.new_tokens)
    dt = time.perf_counter() - t0
    total_new = args.batch * args.new_tokens
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s incl. prefill; "
          f"{engine.last_decode_dispatches} decode dispatches at "
          f"K={args.decode_window})")
    print("first sequence:", np.asarray(out[0]).tolist())


def _fleet_drill(args, cfg, params, reqs):
    """Serve ``reqs`` through a replica fleet and hold it to the
    single-engine bar: every request completes on the survivors with a
    clean outcome, bit-identical to a fault-free single-engine run."""
    import tempfile

    from repro.serve.chaos import ChaosInjector
    from repro.serve.fleet import FleetRouter

    kill_at = tuple(args.chaos_replica_kill_at)
    bitflip_at = tuple(args.chaos_bitflip_at)
    if kill_at and not args.snapshot_every:
        raise SystemExit("--chaos-replica-kill-at needs --snapshot-every "
                         "(handoff resumes from the victim's snapshot)")

    def build():
        return ServeEngine(cfg, params, max_len=args.max_len,
                           decode_window=args.decode_window,
                           paged=args.paged, page_size=args.page_size,
                           pool_pages=args.pool_pages)

    # Fault-free single-engine reference (recoverable=True so the ring
    # sizing — and with it every stream — matches the fleet's sessions).
    baseline = build().serve(
        reqs, slots=args.slots, temperature=args.temperature,
        top_k=args.top_k, eos_id=args.eos_id, seed=args.seed,
        recoverable=True)

    engines = [build() for _ in range(args.replicas)]
    victim = 1 if args.replicas > 1 else 0
    chaos = None
    if kill_at or bitflip_at or args.chaos_seed is not None:
        chaos = [None] * args.replicas
        chaos[victim] = ChaosInjector(
            seed=args.chaos_seed or 0, nan_rate=args.chaos_nan_rate,
            nan_at=tuple(args.chaos_nan_at), bitflip_at=bitflip_at,
            replica_kill_at=kill_at)
    snap_root = args.snapshot_dir or (
        tempfile.mkdtemp(prefix="fleet_snap_") if args.snapshot_every
        else None)
    t0 = time.perf_counter()
    fleet = FleetRouter(
        engines, reqs, slots=args.slots, temperature=args.temperature,
        top_k=args.top_k, eos_id=args.eos_id, seed=args.seed,
        deadline_ms=args.deadline_ms, max_queue=args.max_queue,
        watchdog_timeout_s=args.watchdog_timeout,
        snapshot_every=args.snapshot_every, snapshot_root=snap_root,
        checksum_every=args.checksum_every, chaos=chaos)
    outs = fleet.run()
    dt = time.perf_counter() - t0
    emitted = sum(o.size for o in outs)
    st = fleet.stats
    print(f"fleet served {len(reqs)} requests over {args.replicas} "
          f"replicas ({emitted} tokens) in {dt:.2f}s "
          f"({emitted/dt:.1f} tok/s; {st['rounds']} rounds, "
          f"{st['assignments']} assignments, {st['replica_deaths']} "
          f"deaths, {st['handoffs']} handoffs)")
    per = fleet.stats_by_replica()
    print("per-replica dispatches:",
          [s["decode_dispatches"] for s in per],
          "states:", [m.state for m in fleet.monitors])
    counts: dict[str, int] = {}
    for o in outs:
        counts[o.outcome] = counts.get(o.outcome, 0) + 1
    print("outcomes:", " ".join(
        f"{k}={v}" for k, v in sorted(counts.items())))
    if kill_at:
        if not st["replica_deaths"]:
            raise SystemExit("fleet drill: pinned replica kill never fired")
        if not (st["handoffs"] or st["handoff_requeued_fresh"]):
            raise SystemExit("fleet drill: replica died but nothing was "
                             "handed off or re-queued")
    if bitflip_at and not sum(s["corruptions"] for s in per):
        raise SystemExit("fleet drill: bit flips injected but the "
                         "checksum chain detected none")
    bad = [o.outcome for o in outs
           if o.outcome not in ("ok", "eos", "recovered")]
    if bad:
        raise SystemExit(f"fleet drill: unclean outcomes {bad}")
    for i, (want, got) in enumerate(zip(baseline, outs)):
        if not np.array_equal(np.asarray(want), np.asarray(got)):
            raise SystemExit(
                f"fleet drill: request {i} diverged from the fault-free "
                "single-engine run — handoff broke bit-identity")
    print("fleet drill: all requests completed on survivors, every "
          "stream bit-identical to the fault-free single-engine run")


if __name__ == "__main__":
    main()
