"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

The beyond-baseline §Perf variant (``moe_impl="a2a"``).  The baseline
gather-MoE routes globally: GSPMD must all-gather the token activations
across the mesh before the expert gather, and all-reduce the scatter-add —
O(tokens·d) all-gather bytes per layer.  Here, routing is *local* per data
shard and tokens travel to their experts by ONE all-to-all over the model
axis (and back) — point-to-point producer→consumer delivery, the paper's
elevator/eLDST discipline at ICI level (DeepSpeed-MoE style):

  per shard:  tokens (n_loc, d) --route--> (E, C_loc, d)
  all_to_all: (E, C_loc, d) -> (E_loc, tp·C_loc, d)     [model axis]
  expert FFN on local experts; reverse all_to_all; local weighted combine.

Collective bytes per layer per device drop from O(n_loc·d·tp) (gather) to
2·k·n_loc·cf·d (two a2a passes of the dispatched tokens).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.model.moe import _topk_routing


def apply_moe_a2a(
    params, x: jax.Array, cfg, *, axis_name: str = "model",
    capacity_factor: float | None = None,
):
    """Inside shard_map: x (b_loc, t, d) local tokens; experts sharded on
    ``axis_name``.  Router/expert weights arrive as their local shards."""
    tp = jax.lax.psum(1, axis_name)
    b, t, d = x.shape
    e = cfg.num_experts
    k = cfg.num_experts_per_tok
    e_loc = e // tp
    cf = capacity_factor if capacity_factor is not None else cfg.moe_capacity_factor
    n = b * t
    cap = max(8, int(n * k * cf / e))
    cap = -(-cap // 8) * 8

    xf = x.reshape(n, d)
    # Router weights are replicated across the model axis inside shard_map.
    logits = xf.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    weights, experts = _topk_routing(logits, k)

    flat_expert = experts.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    flat_weight = weights.reshape(-1)

    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_weight = flat_weight[order]
    counts = jnp.bincount(flat_expert, length=e)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n * k, dtype=jnp.int32) - starts[sorted_expert]
    keep = pos < cap
    slot = jnp.where(keep, sorted_expert * cap + pos, e * cap)

    disp_tok = jnp.zeros(e * cap + 1, jnp.int32).at[slot].set(sorted_token + 1)
    disp_w = jnp.zeros(e * cap + 1, jnp.float32).at[slot].set(sorted_weight)
    disp_tok = disp_tok[: e * cap].reshape(e, cap)
    disp_w = disp_w[: e * cap].reshape(e, cap)

    valid = disp_tok > 0
    xe = jnp.take(xf, jnp.maximum(disp_tok - 1, 0).reshape(-1), axis=0)
    xe = xe.reshape(e, cap, d)
    xe = jnp.where(valid[..., None], xe, 0.0)

    # ---- point-to-point dispatch: tokens travel to their expert's shard ----
    # local (E, C, d): expert-major rows; tiled a2a sends the rows of expert
    # group j to device j and concatenates received sender blocks along the
    # capacity axis -> (e_loc, tp*C, d), slot = sender*C + c.
    xe = jax.lax.all_to_all(
        xe, axis_name, split_axis=0, concat_axis=1, tiled=True
    )

    if cfg.mlp_type == "geglu":
        act = lambda g: jax.nn.gelu(g, approximate=True)
    else:
        act = jax.nn.silu
    # Local expert weights: (e_loc, d, f) shards of the stacked tensors.
    h = act(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", xe, params["w_up"]
    )
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    # ---- return trip: inverse tiled exchange, back to expert-major layout --
    ye = jax.lax.all_to_all(ye, axis_name, split_axis=1, concat_axis=0,
                            tiled=True)  # (e, cap, d)

    ye = ye * disp_w[..., None]
    ye = jnp.where(valid[..., None], ye, 0.0)
    out = jnp.zeros((n + 1, d), ye.dtype).at[disp_tok.reshape(-1)].add(
        ye.reshape(-1, d)
    )[1:]
    return out.reshape(b, t, d).astype(x.dtype)


def apply_moe_sharded(params, x: jax.Array, cfg):
    """pjit-callable wrapper: runs :func:`apply_moe_a2a` under shard_map
    using the active sharding context.  Falls back to the gather path when
    no mesh/model axis is active (CPU tests) or batch doesn't divide."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from repro.model import moe as moe_mod
    from repro.model.sharding import _CTX

    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return moe_mod.apply_moe(params, x, cfg)
    data = rules.get("batch")
    data_size = 1
    if data:
        axes = data if isinstance(data, tuple) else (data,)
        for a in axes:
            data_size *= mesh.shape[a]
    if data_size == 0 or x.shape[0] % max(data_size, 1):
        return moe_mod.apply_moe(params, x, cfg)
    tp = mesh.shape["model"]
    if cfg.num_experts % tp or x.shape[1] % tp:
        return moe_mod.apply_moe(params, x, cfg)

    # Tokens sequence-sharded over the model axis (SP): every device routes
    # a distinct 1/tp of the tokens — no replicated routing work.
    x_spec = P(data, "model", None)
    param_specs = {
        "router": P(None, None),             # replicated (tiny)
        "w_gate": P("model", None, None),    # local experts
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    # Replication checking off: the a2a writes are deliberately uneven.
    f = jax.shard_map(
        partial(apply_moe_a2a, cfg=cfg, axis_name="model"),
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    return f(params, x)
