"""Model building blocks: norms, MLPs, embeddings, rotary embeddings."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.model.sharding import constrain, gather_for_use


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def init_rmsnorm(mk, d: int, name: str):
    return {"scale": mk(f"{name}.scale", (d,), ("act_embed",), "ones")}


def rms_norm(params, x: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def init_mlp(mk, cfg, name: str):
    d, f = cfg.d_model, cfg.d_ff
    p = {}
    if cfg.mlp_type in ("swiglu", "geglu"):
        p["w_gate"] = mk(f"{name}.w_gate", (d, f), ("embed", "ff"))
        p["w_up"] = mk(f"{name}.w_up", (d, f), ("embed", "ff"))
    else:  # relu2 (nemotron): no gating
        p["w_up"] = mk(f"{name}.w_up", (d, f), ("embed", "ff"))
    p["w_down"] = mk(f"{name}.w_down", (f, d), ("ff", "embed"))
    return p


def apply_mlp(params, x: jax.Array, cfg) -> jax.Array:
    g = cfg.fsdp_gather_weights
    w_up = gather_for_use(params["w_up"], ("embed", "ff"), g)
    w_down = gather_for_use(params["w_down"], ("ff", "embed"), g)
    if cfg.mlp_type == "swiglu":
        w_gate = gather_for_use(params["w_gate"], ("embed", "ff"), g)
        h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    elif cfg.mlp_type == "geglu":
        w_gate = gather_for_use(params["w_gate"], ("embed", "ff"), g)
        h = jax.nn.gelu(x @ w_gate, approximate=True) * (x @ w_up)
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(x @ w_up))
    else:
        raise ValueError(cfg.mlp_type)
    h = constrain(h, "batch", "seq", "act_ff")
    return h @ w_down


# --------------------------------------------------------------------------
# Embeddings / logits
# --------------------------------------------------------------------------

def init_embeddings(mk, cfg, name: str = "tok"):
    v = cfg.padded_vocab
    p = {"embedding": mk(f"{name}.embedding", (v, cfg.d_model),
                         ("vocab", "embed"), "normal", 0.02)}
    if not cfg.tie_embeddings:
        p["unembed"] = mk(f"{name}.unembed", (cfg.d_model, v),
                          ("embed", "vocab"))
    return p


def embed_tokens(params, tokens: jax.Array, cfg) -> jax.Array:
    x = jnp.take(params["embedding"], tokens, axis=0)
    return constrain(x.astype(cfg.dtype), "batch", "seq", "act_embed")


def logits_projection(params, x: jax.Array, cfg) -> jax.Array:
    if cfg.tie_embeddings:
        w = gather_for_use(
            params["embedding"], ("vocab", "embed"), cfg.fsdp_gather_weights
        ).T
    else:
        w = gather_for_use(
            params["unembed"], ("embed", "vocab"), cfg.fsdp_gather_weights
        )
    logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.padded_vocab != cfg.vocab_size:
        # Mask pad rows so they can never win the softmax/argmax.
        vid = jnp.arange(cfg.padded_vocab)
        logits = jnp.where(vid >= cfg.vocab_size, -1e30, logits)
    return constrain(logits, "batch", "seq", "vocab")


# --------------------------------------------------------------------------
# Rotary embeddings (RoPE + qwen2-vl M-RoPE)
# --------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: tuple[int, ...] | None = None) -> jax.Array:
    """x: (B, H, T, D).  positions: (B, T) or (3, B, T) for M-RoPE.

    M-RoPE (qwen2-vl): the half-dim frequency bands are partitioned into
    (temporal, height, width) sections; each section rotates by its own
    positional stream.  Text tokens carry identical t/h/w positions, making
    M-RoPE degenerate to 1D RoPE for them.
    """
    b, h, t, d = x.shape
    half = d // 2
    freqs = rope_frequencies(d, theta)  # (half,)

    if mrope_sections is not None:
        if positions.ndim == 2:
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        assert sum(mrope_sections) == half, (mrope_sections, half)
        section_id = jnp.repeat(
            jnp.arange(len(mrope_sections)),
            jnp.asarray(mrope_sections),
            total_repeat_length=half,
        )  # (half,) which positional stream each band uses
        pos = positions.astype(jnp.float32)  # (3, B, T)
        # angle[b, t, i] = pos[section_id[i], b, t] * freqs[i]
        angle = jnp.take(pos, section_id, axis=0)            # (half, B, T)
        angle = jnp.moveaxis(angle, 0, -1) * freqs           # (B, T, half)
    else:
        pos = positions.astype(jnp.float32)                  # (B, T)
        angle = pos[:, :, None] * freqs                      # (B, T, half)

    cos = jnp.cos(angle)[:, None, :, :]  # (B, 1, T, half)
    sin = jnp.sin(angle)[:, None, :, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
