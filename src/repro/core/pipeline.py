"""Pipeline parallelism as elevator forwarding over the stage axis.

GPipe-style microbatch pipelining where the stage-to-stage activation hand-off
is a ``ppermute`` shift (Δ=+1 over the stage axis) — a device-space elevator
node.  Bubble slots are the elevator's boundary constant: stages with no
producer receive zeros and their output is masked out of the final result.

Runs inside ``shard_map`` over the stage axis; the layer weights of stage
``i`` live only on shard ``i`` (the caller shards the stacked stage params).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import device_comm

__all__ = ["pipeline_apply"]


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x_micro: jax.Array,
    axis_name: str,
):
    """Run ``stage_fn`` as a ``num_stages``-deep pipeline over microbatches.

    Args:
      stage_fn: ``(params, x) -> y`` — one pipeline stage (a block of layers).
      stage_params: this shard's stage parameters (already stage-sharded).
      x_micro: ``(num_micro, micro_batch, ...)`` microbatched input. Every
        shard holds the full microbatch stream; only stage 0 injects it.
      axis_name: mesh axis carrying the stages.

    Returns:
      ``(num_micro, micro_batch, ...)`` outputs of the final stage (valid on
      every shard; non-final shards hold garbage that the caller discards —
      conventionally the result is psum-masked to the last stage's value).

    Schedule: ``num_micro + num_stages - 1`` ticks.  At tick ``t`` stage
    ``s`` processes microbatch ``t - s`` (if in range).  The activation
    hand-off is one collective-permute per tick — point-to-point, no global
    barrier, exactly the paper's producer/consumer firing rule.
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    buf_shape = x_micro.shape[1:]

    def tick(carry, t):
        incoming, outputs = carry
        # Stage 0 injects microbatch t (bubble = zeros once the stream ends).
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        injected = jax.lax.dynamic_index_in_dim(x_micro, mb_idx, axis=0, keepdims=False)
        injected = jnp.where(t < n_micro, injected, jnp.zeros(buf_shape, x_micro.dtype))
        x_in = jnp.where(stage == 0, injected, incoming)

        y = stage_fn(stage_params, x_in)

        # Final stage commits microbatch t - (n_stages - 1) to the output.
        out_idx = t - (n_stages - 1)
        valid_out = (out_idx >= 0) & (stage == n_stages - 1)
        safe_idx = jnp.clip(out_idx, 0, n_micro - 1)
        committed = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(valid_out, y, outputs[safe_idx]), safe_idx, axis=0
        )
        # Elevator hand-off to the next stage (boundary shards get zeros).
        nxt = device_comm.device_shift(y, axis_name, delta=1, fill=0.0)
        return (nxt, committed), None

    init_in = jnp.zeros(buf_shape, x_micro.dtype)
    init_out = jnp.zeros_like(x_micro)
    # The loop-carried buffers become shard-varying after the first ppermute;
    # mark them varying up front so the scan carry types are stable.
    init_in = jax.lax.pcast(init_in, (axis_name,), to="varying")
    init_out = jax.lax.pcast(init_out, (axis_name,), to="varying")
    (_, outputs), _ = jax.lax.scan(tick, (init_in, init_out), jnp.arange(ticks))
    return outputs
