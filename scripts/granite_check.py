#!/usr/bin/env python3
"""granite-4.0-h-micro on the chip: the SSD kernels and the served path
against their references, at the benchmark cell's sizes.

    python scripts/granite_check.py            # a TPU; published widths
    python scripts/granite_check.py --tiny     # any backend; reduced model

1. Each SSD kernel (``ssd_chunk_pallas``, ``ssd_decode_window_pallas``)
   compiled natively, against the sequential float32 recurrence
   (``kernels/ssd/ref.py``) at 64 heads of 64, d_state 128: a 300-token
   prefill from a nonzero state (three chunks, the last one partial) and
   windows of 1, 8 and 64 tokens, with decays from exp(-0.05) to below
   exp(-20) a token.  Error: the largest absolute difference over the
   largest absolute value of the reference, for y and the state.  Then
   the decode window over 32 rows with three live and with none: the
   live rows as above, every dead row (one of them NaN) bit for bit and
   its y zero (the changed values counted, limit 0).
2. Prefill then decode through the decode state, as the engine's admit
   and window programs do (``model.decode_step`` under jit: a masked
   320-token admission of two prompts, 300 and 40 tokens, then 16 single
   token steps), with the cell's weights (``bench/weights.py``, seed
   below) and state (32 slots, 1024 positions), against the plain
   float32 reference's full forward pass
   (``bench/configs/granite-4.0-h-micro.ref.py``) on logits.  Error: the
   largest absolute logit difference over the reference logits' standard
   deviation at that position.  Its limit is half the same error of the
   reference's float8 control (every matmul operand in float8): the
   random model carries rounding through 36 Mamba layers far, so no
   fixed bound tells bf16 from a lower precision, and this one does.

The last line is a JSON object with every error beside its limit and
``ok``; the exit code is 0 only if every error is within its limit.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: Kernel limits, relative to the reference's largest value.  The state
#: is f32 end to end (the chunk kernel's MXU products at full f32
#: precision): a bf16 state fails its limit (2^-9 relative rounding a
#: token).  The output is bf16: its rounding moves a value by up to 2^-8.
STATE_TOL = 1e-3
OUT_TOL = 2 ** -8
#: Served logits against the float32 reference, as a share of the float8
#: control's error.
LOGIT_SHARE = 0.5
SEED = 2**31 + 16


def kernel_errors(tiny: bool) -> dict:
    from repro.kernels.ssd.decode import ssd_decode_window_pallas
    from repro.kernels.ssd.kernel import ssd_chunk_pallas
    from repro.kernels.ssd.ref import (random_inputs, rel_error,
                                       ssd_sequential_ref)

    shape = (2, 8, 16, 16) if tiny else (2, 64, 64, 128)
    interp = jax.default_backend() != "tpu"
    out = {}
    for name, fn, t in (("ssd_chunk", ssd_chunk_pallas, 300),
                        ("ssd_decode_window", ssd_decode_window_pallas, 1),
                        ("ssd_decode_window", ssd_decode_window_pallas, 8),
                        ("ssd_decode_window", ssd_decode_window_pallas, 64)):
        args = random_inputs(shape, t, seed=t)
        y, s = fn(*args[:5], args[5][None], 0, interpret=interp)
        y0, s0 = ssd_sequential_ref(*args)
        out[f"{name}.t{t}.y"] = rel_error(y, y0)
        out[f"{name}.t{t}.state"] = rel_error(s[0], s0)
    out.update(live_row_errors(tiny, interp))
    return out


def live_row_errors(tiny: bool, interp: bool) -> dict:
    """The decode window over 32 rows of which three are live, one dead
    row's state NaN, and over none live: the live rows against the
    recurrence; dead rows' state bits changed and y values nonzero,
    counted (their limit is 0)."""
    from repro.kernels.ssd.decode import ssd_decode_window_pallas
    from repro.kernels.ssd.ref import (random_inputs, rel_error,
                                       ssd_sequential_ref)

    shape = (32, 8, 16, 16) if tiny else (32, 64, 64, 128)
    out = {}
    for name, rows in (("three", (0, 5, 31)), ("none", ())):
        x, dt, a, bm, cm, h0 = random_inputs(shape, 1, seed=7)
        live = np.isin(np.arange(32), rows)
        h0 = h0.at[1, 0, 0, 0].set(jnp.nan)
        y, s = ssd_decode_window_pallas(x, dt, a, bm, cm, h0[None], 0,
                                        jnp.asarray(live), interpret=interp)
        if live.any():
            y0, s0 = ssd_sequential_ref(x[live], dt[live], a, bm[live],
                                        cm[live], h0[live])
            out[f"live_rows.{name}.y"] = rel_error(y[live], y0)
            out[f"live_rows.{name}.state"] = rel_error(s[0][live], s0)
        bits = lambda v: np.asarray(v, np.float32).view(np.uint32)  # noqa: E731
        out[f"live_rows.{name}.dead_state_changed"] = float(np.sum(
            bits(s[0][~live]) != bits(h0[~live])))
        out[f"live_rows.{name}.dead_y_nonzero"] = float(np.sum(
            np.asarray(y[~live], np.float32) != 0))
    return out


def served_errors(tiny: bool) -> dict:
    from bench import harness, weights
    from repro.model import model as M
    from repro.serve.replay import teacher_forced_logits

    cfg = harness.arch_config(json.loads(
        (ROOT / "bench/configs/granite-4.0-h-micro.json").read_text()))
    slots, max_len, lens, new = 32, 1024, (300, 40), 16
    if tiny:
        cfg = dataclasses.replace(cfg.reduced(), dtype="bfloat16")
        slots, max_len = 4, 512
    ref = harness.load_module(ROOT / "bench/configs/granite-4.0-h-micro.ref.py")
    params = weights.draw(M.abstract_params(cfg), harness.jax_seed(SEED))
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, cfg.vocab_size, n + new).astype(np.int32)
            for n in lens]
    got = teacher_forced_logits(params, cfg, seqs, lens, new, slots=slots,
                                max_len=max_len)
    sizes = harness.sizes_of(cfg)
    worst = {"served": 0.0, "fp8": 0.0}
    for s, n, mine in zip(seqs, lens, got):
        pad = np.zeros((1, -(-s.size // 128) * 128), np.int32)
        pad[0, :s.size] = s
        rows = slice(n - 1, n - 1 + new)
        want = ref.head(params, sizes,
                        ref.hidden(params, sizes, jnp.asarray(pad))[0, rows])
        fp8 = ref.head(params, sizes, ref.hidden(
            params, sizes, jnp.asarray(pad), "fp8")[0, rows], "fp8")
        for k, v in (("served", mine), ("fp8", fp8)):
            d = jnp.max(jnp.abs(v - want), -1) / jnp.std(want, -1)
            worst[k] = max(worst[k], float(jnp.max(d)))
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args(argv)
    if not a.tiny and jax.default_backend() != "tpu":
        print("no TPU: run with --tiny off the chip", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    errs = kernel_errors(a.tiny)
    print(json.dumps(errs), file=sys.stderr, flush=True)
    served = served_errors(a.tiny)
    checks = {k: {"value": v, "limit": STATE_TOL if k.endswith("state")
                  else 0.0 if k.startswith("live_rows.") and "dead" in k
                  else OUT_TOL} for k, v in errs.items()}
    checks["served_logits_sd"] = {"value": served["served"],
                                  "limit": LOGIT_SHARE * served["fp8"]}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    dev = jax.devices()[0]
    print(json.dumps({"ok": ok, "checks": checks, "seconds":
                      time.perf_counter() - t0,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
