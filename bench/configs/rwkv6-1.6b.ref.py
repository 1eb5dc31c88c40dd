"""Plain reference for the RWKV-6 block as the configuration runs it.

Float32 ``jax.numpy`` at ``highest`` matmul precision, one layer at a
time, the WKV recurrence as a sequential scan over tokens; no kernels,
no cache, no batching tricks.  The equations (arXiv:2404.05892, with the
departures the served model makes, listed in PERF.md):

    h      = rmsnorm(x) ;  h_prev = h shifted one token (zeros first)
    x_j    = h + (h_prev - h) * mu_j          j in r, k, v, g, w
    r, k, v = x_r W_r, x_k W_k, x_v W_v ;  g = silu(x_g W_g)
    w      = exp(-exp(clip(base + tanh(x_w A) B, -6, 1.386)))
    S_t    = diag(w_t) S_{t-1} + k_t^T v_t       per 64-wide head
    o_t    = r_t (S_{t-1} + diag(u) k_t^T v_t)
    x      = x + (rmsnorm(o) * g) W_o
    x      = x + swiglu(rmsnorm(x))
    logits = rmsnorm(x) W_unembed

``precision="fp8"`` is the control: every matmul operand is scaled per
row or column and rounded to float8 e4m3 before the product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HEAD = 64
F32 = jnp.float32


def _q8(a, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(a, b, precision):
    a, b = a.astype(F32), b.astype(F32)
    if precision == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def _wkv(r, k, v, w, u):
    """(B, T, H, D) float32 each, u (H, D) -> out (B, T, H, D)."""
    hp = jax.lax.Precision.HIGHEST

    def step(s, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        out = jnp.einsum("bhd,bhde->bhe", rt, s + u[None, :, :, None] * kv,
                         precision=hp)
        return s * wt[..., None] + kv, out

    b, _, h, d = r.shape
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    _, out = jax.lax.scan(step, jnp.zeros((b, h, d, d), F32), xs)
    return jnp.moveaxis(out, 0, 1)


def _layer(x, blocks, i, eps, precision):
    p = jax.tree.map(lambda a: a[i], blocks)
    tm, ffn = p["rwkv"], p["ffn"]
    b, t, d = x.shape
    h = rmsnorm(x, p["ln1"]["scale"], eps)
    prev = jnp.concatenate([jnp.zeros((b, 1, d), F32), h[:, :-1]], axis=1)
    mu = tm["mu"].astype(F32)
    xr, xk, xv, xg, xw = (h + (prev - h) * mu[j] for j in range(5))
    r = mm(xr, tm["w_r"], precision)
    k = mm(xk, tm["w_k"], precision)
    v = mm(xv, tm["w_v"], precision)
    g = jax.nn.silu(mm(xg, tm["w_g"], precision))
    logit = tm["w_decay_base"].astype(F32) + mm(
        jnp.tanh(mm(xw, tm["w_decay_lora_a"], precision)),
        tm["w_decay_lora_b"], precision)
    w = jnp.exp(-jnp.exp(jnp.clip(logit, -6.0, 1.386)))
    heads = lambda z: z.reshape(b, t, d // HEAD, HEAD)  # noqa: E731
    o = _wkv(heads(r), heads(k), heads(v), heads(w),
             tm["u_bonus"].astype(F32).reshape(d // HEAD, HEAD))
    o = rmsnorm(o.reshape(b, t, d), tm["out_norm"]["scale"], eps) * g
    x = x + mm(o, tm["w_o"], precision)
    h = rmsnorm(x, p["ln2"]["scale"], eps)
    f = jax.nn.silu(mm(h, ffn["w_gate"], precision)) * mm(h, ffn["w_up"], precision)
    return x + mm(f, ffn["w_down"], precision)


_layer_jit = jax.jit(_layer, static_argnums=(3, 4))


def hidden(params, sizes, tokens, precision="f32"):
    """Final-normed hidden states (B, T, d) float32 for ``tokens`` (B, T)."""
    dec = params["decoder"]
    if dec["remainder"] or len(dec["scanned"]) != 1 or "rwkv" not in dec["scanned"][0]:
        raise ValueError("reference expects one scanned RWKV block per layer")
    blocks = dec["scanned"][0]
    eps = float(sizes["norm_eps"])
    emb = params["tok"]["embedding"]
    x = jnp.take(emb, tokens, axis=0).astype(F32) * F32(sizes["d_model"] ** 0.5)
    for i in range(int(sizes["num_layers"])):
        x = _layer_jit(x, blocks, i, eps, precision)
    return rmsnorm(x, params["final_norm"]["scale"], eps)


def head(params, sizes, rows, precision="f32"):
    """Logits (N, vocab) float32 for final hidden rows (N, d)."""
    return mm(rows, params["tok"]["unembed"], precision)[:, : int(sizes["vocab_size"])]
