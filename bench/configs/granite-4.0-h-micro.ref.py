"""Plain reference for Granite-4.0-H-Micro as the configuration runs it.

Float32 ``jax.numpy`` at ``highest`` matmul precision, one layer at a
time, over the whole sequence; no kernels, no cache, no chunking.  The
equations (the published ``granitemoehybrid`` model: Mamba-2, arXiv:
2405.21060, beside GQA attention):

    x      = embed(tokens) * 12                          (embedding_multiplier)
    each of the 40 layers (mamba x5, attn, mamba x4, four times):
      x    = x + 0.22 * mixer(rmsnorm(x))                (residual_multiplier)
      x    = x + 0.22 * swiglu(rmsnorm(x))               (8192 wide)
    logits = rmsnorm(x) embed^T / 8                      (tied; logits_scaling)

    Mamba-2 mixer (64 heads of 64, d_state 128, one group, conv width 4):
      z, xBC, dt = h W_z, h W_xbc, h W_dt                (the input projection)
      xBC  = silu(sum_k conv_w[k] xBC[t-k] + conv_b)     (causal, zero history)
      x, B, C = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
      S_t  = exp(A dt_t) S_{t-1} + dt_t x_t B_t^T        (a plain scan over t)
      y_t  = S_t C_t + D x_t
      out  = rmsnorm(y * silu(z)) W_out                  (gated norm over 4096)

    attention mixer (32 query, 8 KV heads of 64, no bias, no RoPE):
      o    = softmax(0.015625 q k^T + causal) v;  out = o W_o

Departures from the published model, all in the weights' form (listed in
``granite-4.0-h-micro.json`` under ``assumed`` too): the weights are
random; the input projection is three matrices (z, xBC, dt) and
``A_log``, ``dt_bias``, ``D`` and the conv bias (1, n) rows;
``conv_w[k]`` weighs the input k tokens back (the published depthwise
conv1d kernel reversed, the same function); the gated norm's weight is a
leaf named ``scale``.

``precision="fp8"`` is the control: every matmul operand, and the SSD's
x, B and C, scaled per row or column and rounded to float8 e4m3.
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


def ssd_scan(x, dt, a, bm, cm):
    """x (B, T, H, P), dt (B, T, H), a (H,), bm/cm (B, T, N): the
    recurrence token by token from a zero state.  Returns y (B, T, H, P)."""
    b, _, h, p = x.shape

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = (jnp.exp(a * dtt)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return s, jnp.einsum("bhpn,bn->bhp", s, ct, precision=HP)

    s0 = jnp.zeros((b, h, p, bm.shape[-1]), F32)
    _, ys = jax.lax.scan(step, s0, tuple(jnp.moveaxis(v, 1, 0)
                                         for v in (x, dt, bm, cm)))
    return jnp.moveaxis(ys, 0, 1)


def _mamba(x, p, sz, precision):
    eps, nh, hp, n, width, mult = sz
    b, t, _ = x.shape
    di = nh * hp
    h = rmsnorm(x, p["ln1"]["scale"], eps)
    m = p["mamba"]
    z, xbc, dtr = (mm(h, m[w], precision) for w in ("w_z", "w_xbc", "w_dt"))
    hist = jnp.concatenate([jnp.zeros((b, width - 1, xbc.shape[-1]), F32), xbc], 1)
    w = m["conv_w"].astype(F32)
    conv = sum(w[k] * hist[:, width - 1 - k:width - 1 - k + t] for k in range(width))
    xbc = jax.nn.silu(conv + m["conv_b"].astype(F32))
    xs, bm, cm = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    if precision == "fp8":
        xs, bm, cm = _q8(xs, -1), _q8(bm, -1), _q8(cm, -1)
    dt = jax.nn.softplus(dtr + m["dt_bias"].astype(F32))
    a = -jnp.exp(m["A_log"].astype(F32)[0])
    xh = xs.reshape(b, t, nh, hp)
    y = ssd_scan(xh, dt, a, bm, cm) + m["D"].astype(F32)[0, :, None] * xh
    g = y.reshape(b, t, di) * jax.nn.silu(z)
    out = mm(rmsnorm(g, m["norm"]["scale"], eps), m["w_out"], precision)
    return _mlp(x + mult * out, p, eps, mult, precision)


def _attn(x, p, sz, precision):
    eps, hq, hkv, hd, scale, mult = sz
    at = p["attn"]
    b, t, _ = x.shape
    h = rmsnorm(x, p["ln1"]["scale"], eps)
    split = lambda z, n: z.reshape(b, t, n, hd).transpose(0, 2, 1, 3)  # noqa: E731
    q = split(mm(h, at["wq"], precision), hq)
    k = jnp.repeat(split(mm(h, at["wk"], precision), hkv), hq // hkv, axis=1)
    v = jnp.repeat(split(mm(h, at["wv"], precision), hkv), hq // hkv, axis=1)
    if precision == "fp8":
        q, k = _q8(q, -1), _q8(k, -1)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, precision=HP) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if precision == "fp8":
        pr, v = _q8(pr, -1), _q8(v, -2)
    o = jnp.einsum("bhts,bhsd->bhtd", pr, v, precision=HP)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, hq * hd)
    return _mlp(x + mult * mm(o, at["wo"], precision), p, eps, mult, precision)


def _mlp(x, p, eps, mult, precision):
    ffn = p["ffn"]
    h = rmsnorm(x, p["ln2"]["scale"], eps)
    f = jax.nn.silu(mm(h, ffn["w_gate"], precision)) * mm(h, ffn["w_up"], precision)
    return x + mult * mm(f, ffn["w_down"], precision)


_LAYERS = {"mamba": jax.jit(_mamba, static_argnums=(2, 3)),
           "attn": jax.jit(_attn, static_argnums=(2, 3))}


def hidden(params, sizes, tokens, precision="f32"):
    """Final-normed hidden states (B, T, d) float32 for ``tokens`` (B, T)."""
    dec = params["decoder"]
    pattern = tuple(sizes["pattern"])
    if dec["remainder"] or len(dec["scanned"]) != len(pattern):
        raise ValueError("reference expects whole periods of the pattern")
    eps, mult = float(sizes["norm_eps"]), float(sizes["residual_multiplier"])
    sz = {"mamba": (eps, int(sizes["mamba_heads"]), int(sizes["mamba_head_dim"]),
                    int(sizes["mamba_d_state"]), int(sizes["conv_width"]), mult),
          "attn": (eps, int(sizes["num_heads"]), int(sizes["num_kv_heads"]),
                   int(sizes["head_dim"]), float(sizes["attention_multiplier"]),
                   mult)}
    emb = params["tok"]["embedding"]
    x = jnp.take(emb, tokens, axis=0).astype(F32) * F32(sizes["embedding_multiplier"])
    for i in range(int(sizes["num_layers"])):
        kind = pattern[i % len(pattern)]
        p = jax.tree.map(lambda a: a[i // len(pattern)],
                         dec["scanned"][i % len(pattern)])
        x = _LAYERS[kind](x, p, sz[kind], precision)
    return rmsnorm(x, params["final_norm"]["scale"], eps)


def head(params, sizes, rows, precision="f32"):
    """Logits (N, vocab) float32 for final hidden rows (N, d): the tied
    embedding, transposed, over the logits scaling."""
    logits = mm(rows, params["tok"]["embedding"].T, precision)
    return logits[:, : int(sizes["vocab_size"])] / F32(sizes["logits_scaling"])
