"""Training launcher: real execution on available devices, full FT loop.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 100 --ckpt-dir /tmp/ckpt

On the CPU container this drives the reduced (smoke) configs end-to-end —
the same code path a TPU job uses, minus mesh size.  Fault tolerance comes
from ft.watchdog.run_with_restarts + checkpoint.AsyncSaver; the data
pipeline is stateless (step-keyed), so restarts never skip or repeat data.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.configs.registry import get_config
from repro.data.pipeline import DataConfig, make_batch
from repro.ft.watchdog import run_with_restarts
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw
from repro.train import step as train_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if cfg.frontend or cfg.is_enc_dec:
        # Text-only training driver; frontend archs train their backbone on
        # token streams (stub embeddings are a serving-time input).
        cfg = dataclasses.replace(cfg, frontend=None)

    dcfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=args.seed,
    )
    opt_cfg = adamw.AdamWConfig(
        lr=args.lr, warmup_steps=max(args.steps // 20, 1), total_steps=args.steps
    )
    # Donated TrainState: params + optimizer moments update in place
    # instead of copying two model-sized trees per step (and the
    # repro.analysis donation pass audits exactly this entrypoint).
    step_fn = train_mod.make_jitted_train_step(
        cfg, opt_cfg, compress=args.compress_grads
    )
    saver = ckpt.AsyncSaver()
    metrics_log = []

    def make_state():
        return train_mod.init_train_state(
            cfg, jax.random.key(args.seed), compress=args.compress_grads
        )

    def do_step(state, step):
        if cfg.is_enc_dec:
            batch = make_batch(dcfg, step)
            batch["enc_embeds"] = jnp.zeros(
                (args.batch, args.seq, cfg.d_model), jnp.dtype(cfg.dtype)
            )
        else:
            batch = make_batch(dcfg, step)
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        metrics_log.append((step, loss))
        if step % args.log_every == 0:
            tok_s = args.batch * args.seq / dt
            print(f"step {step:5d}  loss {loss:.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"{tok_s:,.0f} tok/s", flush=True)
        return state

    def save_fn(state, step):
        if args.ckpt_dir:
            saver.save_async(args.ckpt_dir, step, state)

    def restore_fn():
        if not args.ckpt_dir:
            return None
        latest = ckpt.latest_step(args.ckpt_dir)
        if latest is None:
            return None
        template = make_state()
        state, step = ckpt.restore(args.ckpt_dir, template)
        return state, step

    state, stats = run_with_restarts(
        make_state=make_state,
        step_fn=do_step,
        save_fn=save_fn,
        restore_fn=restore_fn,
        num_steps=args.steps,
        checkpoint_every=args.ckpt_every,
        watchdog_timeout_s=1800.0,
        on_event=lambda m: print(f"[ft] {m}", flush=True),
    )
    saver.wait()
    first = metrics_log[0][1] if metrics_log else float("nan")
    last = metrics_log[-1][1] if metrics_log else float("nan")
    print(f"done: steps={stats['steps_run']} restarts={stats['restarts']} "
          f"loss {first:.4f} -> {last:.4f}", flush=True)


if __name__ == "__main__":
    main()
