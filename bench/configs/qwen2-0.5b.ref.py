"""Plain reference for Qwen2 as the configuration runs it.

Float32 ``jax.numpy`` at ``highest`` matmul precision, one layer at a
time, full causal attention over the whole sequence; no kernels, no
cache.  The equations (Qwen2, arXiv:2407.10671, with the departures the
served model makes, listed in PERF.md):

    x      = embed(tokens) * sqrt(d)
    h      = rmsnorm(x)
    q, k, v = h W_q + b_q, h W_k + b_k, h W_v + b_v    (14 query, 2 KV heads)
    q, k   = rope(q), rope(k)                          (rotate-half, theta 1e6)
    o      = softmax(q k^T / sqrt(64) + causal) v      (each KV head serves 7)
    x      = x + o W_o
    x      = x + swiglu(rmsnorm(x))
    logits = rmsnorm(x) embed^T                        (tied)

``precision="fp8"`` is the control: every matmul operand is scaled per
row or column and rounded to float8 e4m3 before the product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HP = jax.lax.Precision.HIGHEST


def _q8(a, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(a, b, precision):
    a, b = a.astype(F32), b.astype(F32)
    if precision == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=HP)


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def rope(x, theta):
    """x (B, H, T, D): rotate the two halves of each head by position."""
    t, d = x.shape[2], x.shape[3]
    half = d // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(x, blocks, i, sz, precision):
    eps, hq, hkv, hd, theta = sz
    p = jax.tree.map(lambda a: a[i], blocks)
    at, ffn = p["attn"], p["ffn"]
    b, t, _ = x.shape
    h = rmsnorm(x, p["ln1"]["scale"], eps)
    split = lambda z, n: z.reshape(b, t, n, hd).transpose(0, 2, 1, 3)  # noqa: E731
    q = split(mm(h, at["wq"], precision) + at["bq"].astype(F32), hq)
    k = split(mm(h, at["wk"], precision) + at["bk"].astype(F32), hkv)
    v = split(mm(h, at["wv"], precision) + at["bv"].astype(F32), hkv)
    q, k = rope(q, theta), rope(k, theta)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    if precision == "fp8":
        q, k = _q8(q, -1), _q8(k, -1)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, precision=HP) / jnp.sqrt(F32(hd))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if precision == "fp8":
        pr, v = _q8(pr, -1), _q8(v, -2)
    o = jnp.einsum("bhts,bhsd->bhtd", pr, v, precision=HP)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, hq * hd)
    x = x + mm(o, at["wo"], precision)
    h = rmsnorm(x, p["ln2"]["scale"], eps)
    f = jax.nn.silu(mm(h, ffn["w_gate"], precision)) * mm(h, ffn["w_up"], precision)
    return x + mm(f, ffn["w_down"], precision)


_layer_jit = jax.jit(_layer, static_argnums=(3, 4))


def hidden(params, sizes, tokens, precision="f32"):
    """Final-normed hidden states (B, T, d) float32 for ``tokens`` (B, T)."""
    dec = params["decoder"]
    if dec["remainder"] or len(dec["scanned"]) != 1 or "attn" not in dec["scanned"][0]:
        raise ValueError("reference expects one scanned attention block per layer")
    blocks = dec["scanned"][0]
    eps = float(sizes["norm_eps"])
    sz = (eps, int(sizes["num_heads"]), int(sizes["num_kv_heads"]),
          int(sizes["head_dim"]), float(sizes["rope_theta"]))
    emb = params["tok"]["embedding"]
    x = jnp.take(emb, tokens, axis=0).astype(F32) * F32(sizes["d_model"] ** 0.5)
    for i in range(int(sizes["num_layers"])):
        x = _layer_jit(x, blocks, i, sz, precision)
    return rmsnorm(x, params["final_norm"]["scale"], eps)


def head(params, sizes, rows, precision="f32"):
    """Logits (N, vocab) float32 for final hidden rows (N, d): the tied
    embedding, transposed."""
    return mm(rows, params["tok"]["embedding"].T, precision)[:, : int(sizes["vocab_size"])]
