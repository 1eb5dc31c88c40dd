#!/usr/bin/env python3
"""Chip smoke: the serving path, end to end, on a TPU.

    python chip_smoke.py             # one chip: phases A and B, kernel check
    python chip_smoke.py --chips 4   # four chips: sequence-parallel prefill

One chip:

* **Phase A** serves ``rwkv6-1.6b`` at its published widths (24 layers,
  d 2048, 32 heads x 64, vocab 65536, bf16; random weights from a
  fixed seed) through ``ServeEngine.serve``: 8 requests on 4 slots,
  decode window 8, 32 new tokens each.  The first four prompts are at
  most 64 tokens (their admission takes the WKV decode-window kernel),
  the last four are longer (the chunked WKV kernel).  The compiled admit
  and decode-window programs must contain ``tpu_custom_call``: the
  Pallas kernels ran, not a jnp reference.
* **Phase B** serves ``gemma3-1b`` at its published widths with paged
  KV storage (``_decode_attention`` and ``_paged_gather``).
* **Kernel check**: at phase A's shapes, ``wkv_fused`` through the
  kernels against the float32 jnp reference, for one prefill window and
  one decode window.

Four chips (``--chips 4``) runs only the sequence-parallel WKV prefill:
``rwkv6-1.6b`` prompts of 4096 tokens served by an engine whose mesh
spans the four chips, against the same prompts on one chip of the same
run.  The WKV op over the four chips and its per-shard segment-summary
kernel must match the float32 reference, the state must land on four
devices, the compiled prefill must carry the WKV state across chips with
collective-permutes rather than by gathering tokens, last-token logits
must agree within twice what bf16 rounding alone moves the one-chip
logits (measured in the same run against float32), and greedy tokens
are compared.

Every phase must pass.  The last line of standard output is then
``{"ok": true, "device": {...}}``; on any failure, or where JAX finds no
TPU, the script exits nonzero without it.  Rates printed on the way are
smoke figures from one short run, not measurements.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402  (after the path: the repo's modules follow)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.kernels.common import on_tpu  # noqa: E402
from repro.kernels.wkv.decode import DECODE_WINDOW_MAX  # noqa: E402
from repro.kernels.wkv.ops import wkv_fused, wkv_fused_summary  # noqa: E402
from repro.kernels.wkv.seqpar import wkv_seqshard  # noqa: E402
from repro.launch import roofline as R  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.model import model as M  # noqa: E402
from repro.model.recurrent import RWKV_HEAD_DIM  # noqa: E402
from repro.model.sharding import make_rules, sharding_context  # noqa: E402
from repro.serve.engine import (  # noqa: E402
    SEQ_PREFILL_MIN_T,
    Request,
    ServeEngine,
    _bucket32,
    make_cache_prefill_step,
)

SEED = 0

#: Kernel check tolerance, as a share of the reference's max |value|.
#: Both sides read the same bf16 inputs; the reference runs in float32
#: at ``highest`` matmul precision, the kernel's MXU passes may round
#: f32 operands to bf16 (relative 2**-8 = 3.9e-3) and ``out`` is stored
#: in bf16 (2**-9).  2e-2 leaves about five bf16 roundings of headroom.
KERNEL_TOL = 2e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------------
# Shared pieces
# --------------------------------------------------------------------------

def random_requests(cfg, lengths, new_tokens, seed):
    rng = np.random.default_rng(seed)
    return [
        Request(tokens=rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
                max_new_tokens=new_tokens)
        for n in lengths
    ]


def check_served(cfg, outs, stats, requests):
    """Every request ends ok/eos with its tokens in vocab; no fault path
    ran."""
    for i, (o, r) in enumerate(zip(outs, requests)):
        if o.outcome not in ("ok", "eos"):
            raise SmokeFailure(f"request {i} ended {o.outcome!r}")
        toks = np.asarray(o.tokens)
        if o.outcome == "ok" and toks.size != r.max_new_tokens:
            raise SmokeFailure(
                f"request {i}: {toks.size} tokens, budget {r.max_new_tokens}")
        if toks.size and (toks.min() < 0 or toks.max() >= cfg.vocab_size):
            raise SmokeFailure(f"request {i}: token out of vocab {toks}")
    for key in ("quarantines", "recoveries", "dispatch_retries"):
        if stats[key]:
            raise SmokeFailure(f"{key} = {stats[key]} (must be 0)")


def serve_phase(cfg, params, requests, *, slots, decode_window, max_len,
                paged=False):
    """Serve ``requests`` through ``ServeEngine.serve`` and check them.
    Returns the engine (its jit caches hold the compiled programs)."""
    eng = ServeEngine(cfg, params, max_len=max_len,
                      decode_window=decode_window, paged=paged)
    t0 = time.perf_counter()
    outs = eng.serve(requests, slots=slots, seed=SEED)
    dt = time.perf_counter() - t0
    stats = eng.last_serve_stats
    check_served(cfg, outs, stats, requests)
    emitted = sum(int(np.asarray(o.tokens).size) for o in outs)
    counts: dict[str, int] = {}
    for o in outs:
        counts[o.outcome] = counts.get(o.outcome, 0) + 1
    log(f"  served {len(requests)} requests, {emitted} tokens in {dt:.1f}s "
        f"including compilation (smoke figure, not a measurement)")
    log(f"  outcomes {counts}; admissions {stats['admissions']}, "
        f"decode dispatches {stats['decode_dispatches']}, quarantines "
        f"{stats['quarantines']}, recoveries {stats['recoveries']}, "
        f"retries {stats['dispatch_retries']}")
    return eng


def abstract(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def compiled_serve_texts(eng, *, slots, prompt_buckets, insert_window):
    """Compiled HLO of the engine's admit jit at each prompt bucket and of
    its decode-window jit (greedy sampling, no EOS: what phase A ran)."""
    sds = jax.ShapeDtypeStruct
    b = slots
    params = abstract(eng.params)
    state = M.abstract_decode_state(eng.cfg, batch=b, max_len=eng.max_len,
                                    insert_window=insert_window)
    vec, bvec = sds((b,), jnp.int32), sds((b,), jnp.bool_)
    cur, key = sds((b, 1), jnp.int32), sds((2,), jnp.uint32)
    texts = {}
    for p in prompt_buckets:
        fn = eng._admit_step(p, 0.0, 0, None)
        texts[f"admit p={p}"] = fn.lower(
            params, state, sds((b, p), jnp.int32), bvec, vec, vec, vec, vec,
            vec, vec, bvec, cur, key).compile().as_text()
    win = eng._serve_window(eng.decode_window, 0.0, 0, None)
    texts[f"window k={eng.decode_window}"] = win.lower(
        params, state, cur, vec, vec, vec, bvec, vec, key).compile().as_text()
    return texts


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# --------------------------------------------------------------------------
# One chip
# --------------------------------------------------------------------------

def rwkv_phase(cfg, *, short_lens, long_lens, new_tokens, slots,
               decode_window, max_len):
    """Phase A: rwkv6 serving; both WKV admission routes; on a TPU, the
    kernels in the compiled programs."""
    if (max(short_lens) > DECODE_WINDOW_MAX
            or min(long_lens) <= DECODE_WINDOW_MAX):
        raise ValueError("short prompts must take the decode-window kernel "
                         "and long ones the chunked kernel")
    params = M.init_params(cfg, jax.random.key(SEED))
    log(f"  weights drawn; peak_bytes_in_use {peak_bytes()}")
    reqs = random_requests(cfg, list(short_lens) + list(long_lens),
                           new_tokens, SEED)
    eng = serve_phase(cfg, params, reqs, slots=slots,
                      decode_window=decode_window, max_len=max_len)
    buckets = sorted({key[0] for key in eng._admits})
    if _bucket32(max(short_lens)) not in buckets or not any(
            p > DECODE_WINDOW_MAX for p in buckets):
        raise SmokeFailure(f"admissions did not reach both WKV routes: "
                           f"prompt buckets {buckets}")
    if on_tpu():
        t0 = time.perf_counter()
        texts = compiled_serve_texts(
            eng, slots=slots, prompt_buckets=buckets,
            insert_window=max(decode_window, _bucket32(max(long_lens))))
        for name, txt in texts.items():
            n = txt.count("tpu_custom_call")
            log(f"  compiled {name}: {n} tpu_custom_call sites")
            if not n:
                raise SmokeFailure(f"compiled {name} has no tpu_custom_call: "
                                   f"the Pallas kernels did not run")
        log(f"  HLO check took {time.perf_counter() - t0:.1f}s")


def gemma_phase(cfg, *, lens, new_tokens, slots, decode_window, max_len):
    """Phase B: paged gemma3 serving."""
    params = M.init_params(cfg, jax.random.key(SEED + 1))
    reqs = random_requests(cfg, lens, new_tokens, SEED + 1)
    eng = serve_phase(cfg, params, reqs, slots=slots,
                      decode_window=decode_window, max_len=max_len,
                      paged=True)
    pg = eng.last_paged_stats
    if pg["page_table_violations"]:
        raise SmokeFailure(
            f"{pg['page_table_violations']} page-table violations")


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def wkv_inputs(cfg, *, batch, t, seed, logit_range=(-6.0, 1.386)):
    """bf16 WKV operands at ``cfg``'s head geometry.  The decay is the
    model's ``exp(-exp(logit))`` with the logit clipped to
    ``logit_range`` (``apply_rwkv_block`` clips to [-6, 1.386])."""
    dh = RWKV_HEAD_DIM
    h = cfg.d_model // dh
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(jax.random.key(seed + t), 6)
    shape = (batch, h, t, dh)
    r, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(dt)
               for kk in ks[:3])
    logit = jnp.clip(jax.random.normal(ks[3], shape), *logit_range)
    w = jnp.exp(-jnp.exp(logit)).astype(dt)
    u = (0.3 * jax.random.normal(ks[4], (h, dh))).astype(dt)
    h0 = jax.random.normal(ks[5], (batch, h, dh, dh), jnp.float32)
    return r, k, v, w, u, h0


def kernel_vs_reference(name, fn, args, tol=KERNEL_TOL, ref=None):
    """``fn(*args, use_kernel=True)`` against ``ref`` (default: ``fn`` with
    ``use_kernel=False``) at ``highest`` matmul precision, output by
    output."""
    got = jax.jit(functools.partial(fn, use_kernel=True))(*args)
    ref = ref or functools.partial(fn, use_kernel=False)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(*args)
    errs = [rel_err(g, w) for g, w in zip(got, want)]
    log(f"  {name}: errors {', '.join(f'{e:.2e}' for e in errs)} "
        f"(tolerance {tol:.0e} of max |ref|)")
    if not all(e <= tol for e in errs):
        raise SmokeFailure(f"{name}: kernel vs reference {errs} > {tol}")


def wkv_kernel_check(cfg, *, batch, lengths, seed):
    """``wkv_fused`` (decode dispatch, the serve path's) through the
    kernels vs the float32 jnp reference, one window per length."""
    for t in lengths:
        kernel_vs_reference(
            f"wkv T={t} (out, state)",
            functools.partial(wkv_fused, chunk=16, decode=True),
            wkv_inputs(cfg, batch=batch, t=t, seed=seed))


def one_chip():
    cfg_a = get_config("rwkv6-1.6b")
    log(f"phase A: {cfg_a.name}, {cfg_a.num_layers} layers, d "
        f"{cfg_a.d_model}, {cfg_a.param_count() / 1e9:.2f}B parameters")
    t0 = time.perf_counter()
    rwkv_phase(cfg_a, short_lens=(20, 37, 52, 64),
               long_lens=(130, 260, 390, 500), new_tokens=32, slots=4,
               decode_window=8, max_len=1024)
    log(f"phase A passed in {time.perf_counter() - t0:.1f}s; "
        f"peak_bytes_in_use {peak_bytes()}")

    log("kernel check at phase A shapes")
    wkv_kernel_check(cfg_a, batch=4, lengths=(512, 8), seed=SEED)

    cfg_b = get_config("gemma3-1b")
    log(f"phase B: {cfg_b.name} paged, {cfg_b.num_layers} layers, d "
        f"{cfg_b.d_model}, {cfg_b.param_count() / 1e9:.2f}B parameters")
    t0 = time.perf_counter()
    gemma_phase(cfg_b, lens=(24, 90, 200, 40), new_tokens=16, slots=4,
                decode_window=8, max_len=512)
    log(f"phase B passed in {time.perf_counter() - t0:.1f}s; "
        f"peak_bytes_in_use {peak_bytes()}")


# --------------------------------------------------------------------------
# Four chips
# --------------------------------------------------------------------------

def seqshard_phase(cfg, mesh, *, batch, prompt_len, new_tokens):
    """Sequence-parallel WKV prefill on ``mesh`` vs the same prompts on
    one device: the WKV op itself, placement, collectives, logits and
    greedy tokens.

    The carry across chips is checked where it shows: the WKV op over
    the mesh against the float32 reference on one device, at slow decays
    (w >= 0.98).  The first tokens of every shard read the carried state
    at full weight, so a wrong carry is an O(1) error there.

    The logits tolerance is measured in the same run.  With random
    weights a one-ulp bf16 difference grows through 24 layers to a large
    share of the logits, so no fixed bf16 tolerance can tell a wrong
    carry from rounding.  ``scale`` is how far bf16 rounding alone moves
    the one-device logits from the same weights and prompts in float32
    at ``highest`` precision.  A sequence-parallel path as accurate as
    the one-device path is within ``scale`` of the float32 logits too, so
    by the triangle inequality within ``2 * scale`` of the one-device
    logits.
    """
    if prompt_len < SEQ_PREFILL_MIN_T:
        raise ValueError(f"prompts under {SEQ_PREFILL_MIN_T} tokens do not "
                         "take the sequence-parallel path")
    n_dev = mesh.devices.size
    shard_t = prompt_len // n_dev
    slow = dict(seed=SEED, logit_range=(-6.0, -4.0))
    kernel_vs_reference(
        f"wkv segment summary T={shard_t} (out, state, a_seg)",
        functools.partial(wkv_fused_summary, chunk=16),
        wkv_inputs(cfg, batch=batch, t=shard_t, **slow))
    kernel_vs_reference(
        f"wkv sequence-parallel T={prompt_len} over {n_dev} chips vs one "
        f"(out, state)",
        functools.partial(wkv_seqshard, mesh=mesh, seq_axis="model",
                          chunk=16),
        wkv_inputs(cfg, batch=batch, t=prompt_len, **slow),
        ref=functools.partial(wkv_fused, chunk=16, use_kernel=False))

    params = M.init_params(cfg, jax.random.key(SEED))
    params_mesh = jax.device_put(params, NamedSharding(mesh, P()))
    rng = np.random.default_rng(SEED)
    tokens = rng.integers(0, cfg.vocab_size, (batch, prompt_len)).astype(
        np.int32)
    plen = np.full((batch,), prompt_len, np.int32)
    max_len = prompt_len + new_tokens
    iw = max(8, prompt_len)

    def prefill(eng):
        state = M.init_decode_state(eng.cfg, batch=batch, max_len=max_len,
                                    insert_window=iw)
        logits, state = eng._prefill(eng.params, state, jnp.asarray(tokens),
                                     jnp.asarray(plen))
        return np.asarray(logits[:, -1], np.float32), state

    eng_one = ServeEngine(cfg, params, max_len=max_len)
    eng_mesh = ServeEngine(cfg, params_mesh, max_len=max_len, mesh=mesh)
    t0 = time.perf_counter()
    ref, _ = prefill(eng_one)
    log(f"  one-chip prefill {time.perf_counter() - t0:.1f}s incl. compile")
    t0 = time.perf_counter()
    got, state = prefill(eng_mesh)
    log(f"  {n_dev}-chip prefill {time.perf_counter() - t0:.1f}s incl. "
        f"compile (smoke figures, not measurements)")

    devs = {len(leaf.sharding.device_set) for leaf in jax.tree.leaves(state)}
    log(f"  mesh prefill state spans {sorted(devs)} devices")
    if max(devs) != n_dev:
        raise SmokeFailure(f"prefill state on {sorted(devs)} devices, "
                           f"not {n_dev}")
    del state

    # Collectives of the compiled sequence-parallel prefill.
    jitted = make_cache_prefill_step(cfg, last_only=True, max_len=max_len)
    with mesh, sharding_context(mesh, make_rules(mesh, "prefill_seq")):
        txt = jitted.lower(
            abstract(params_mesh),
            M.abstract_decode_state(cfg, batch=batch, max_len=max_len,
                                    insert_window=iw),
            jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32),
            jax.ShapeDtypeStruct((batch,), jnp.int32),
        ).compile().as_text()
    coll = R.parse_collective_bytes(txt)
    summary = {op: v for op, v in coll.items() if isinstance(v, dict)}
    log(f"  collectives in the compiled prefill: {summary}")
    if not coll["collective-permute"]["count"]:
        raise SmokeFailure("no collective-permute: the WKV state did not "
                           "hop between chips")
    if coll["all-gather"]["count"]:
        raise SmokeFailure(f"{coll['all-gather']['count']} all-gathers in "
                           "the sequence-parallel prefill")

    reqs = random_requests(cfg, [prompt_len] * batch, new_tokens, SEED)

    def greedy(eng):
        outs = eng.serve(reqs, slots=batch, seed=SEED)
        check_served(cfg, outs, eng.last_serve_stats, reqs)
        return [np.asarray(o.tokens) for o in outs]

    streams = [greedy(eng_one), greedy(eng_mesh)]
    del eng_mesh, params_mesh

    # Rounding scale of the one-device path (see the docstring).
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    del eng_one, params
    with jax.default_matmul_precision("highest"):
        ref32, _ = prefill(ServeEngine(cfg32, params32, max_len=max_len))
    del params32
    scale = rel_err(ref, ref32)
    err = rel_err(got, ref)
    log(f"  last-token logits: {n_dev}-chip vs one-chip err {err:.2e} "
        f"(tolerance 2x {scale:.2e}, the one-chip bf16 vs float32 err); "
        f"{n_dev}-chip vs float32 err {rel_err(got, ref32):.2e}")
    if not err <= 2 * scale:
        raise SmokeFailure(f"{n_dev}-chip logits differ from one chip by "
                           f"{err:.3e}, more than twice what bf16 rounding "
                           f"moves the one-chip path ({scale:.3e})")

    same = sum(int(np.sum(a == b)) for a, b in zip(*streams))
    total = sum(a.size for a in streams[0])
    log(f"  greedy tokens equal at {same}/{total} positions")
    # A first greedy token may flip only where the one-chip top-2 margin
    # is within the measured logit difference.
    top2 = np.sort(ref, axis=-1)[:, -2:]
    margin = (top2[:, 1] - top2[:, 0]) / float(np.max(np.abs(ref)))
    for i in range(batch):
        if streams[0][i][0] != streams[1][i][0] and margin[i] > err:
            raise SmokeFailure(
                f"request {i}: first greedy token differs "
                f"({streams[1][i][0]} vs {streams[0][i][0]}) with a top-2 "
                f"margin of {margin[i]:.3e} > logit err {err:.3e}")


def four_chips():
    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX sees "
                           f"{len(jax.devices())}")
    cfg = get_config("rwkv6-1.6b")
    mesh = make_debug_mesh(data=1, model=4)
    log(f"phase C: {cfg.name} sequence-parallel prefill, 4096-token "
        f"prompts, mesh {dict(mesh.shape)}")
    t0 = time.perf_counter()
    seqshard_phase(cfg, mesh, batch=2, prompt_len=4096, new_tokens=8)
    log(f"phase C passed in {time.perf_counter() - t0:.1f}s")


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sequence-parallel prefill phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    log(f"device {dev.device_kind} x {len(jax.devices())}; compile cache "
        f"{enable_compile_cache()}")

    try:
        (four_chips if args.chips == 4 else one_chip)()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
