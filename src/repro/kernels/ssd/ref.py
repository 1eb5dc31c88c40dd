"""Pure-jnp oracles for the SSD (Mamba-2) kernels.

Per head ``h`` with a (P, N) state (P = head_dim, N = d_state), B and C
shared by every head (one group), and a scalar decay per head and token:

    a_t = exp(A_h dt_t)                       (A_h < 0, dt_t >= 0)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t

A token with ``dt = 0`` is an identity step: ``a = 1`` and no input.

* :func:`ssd_sequential_ref` — the O(T) scan, the oracle of every test.
* :func:`ssd_chunked_ref` — SSD's chunked form (arXiv:2405.21060 §6): per
  chunk of L tokens, the intra-chunk part as a masked (L, L) matrix
  ``(C B^T) * exp(segsum)`` applied to ``dt x``, the entering state read
  through ``C`` and the exit state written once.  Every exponent is a
  difference ``cs_t - cs_s`` (s <= t) of in-chunk cumulative log-decays,
  so it is <= 0: no clamp on the decay, unlike the WKV decay-ratio form.
  The math the Pallas chunk kernel fuses, and the CPU path for prompts.
* :func:`random_inputs` and :func:`rel_error` — the seeded operands and
  the error measure by which the kernels are held to the oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HP = jax.lax.Precision.HIGHEST


def ssd_sequential_ref(x, dt, A, Bm, Cm, h0):
    """x (Bt, T, H, P); dt (Bt, T, H) f32; A (H,) f32; Bm, Cm (Bt, T, N);
    h0 (Bt, H, P, N) f32.  Returns (y (Bt, T, H, P) f32, S (Bt, H, P, N)
    f32)."""

    def step(S, inp):
        xt, dtt, bt, ct = inp
        a = jnp.exp(dtt * A)[..., None, None]                # (Bt, H, 1, 1)
        v = (dtt[..., None] * xt)[..., None]                 # (Bt, H, P, 1)
        S = a * S + v * bt[:, None, None, :]
        y = jnp.einsum("bhpn,bn->bhp", S, ct, precision=HP)
        return S, y

    xs = tuple(jnp.moveaxis(a.astype(F32), 1, 0) for a in (x, dt, Bm, Cm))
    S, ys = jax.lax.scan(step, h0.astype(F32), xs)
    return jnp.moveaxis(ys, 0, 1), S


def pad_time(t: int, chunk: int) -> int:
    """Tokens of padding that make ``t`` a whole number of chunks."""
    return -t % chunk


def chunk_cumsum(la, chunk: int):
    """Inclusive cumulative sum of ``la`` (Bt, T, H) within each chunk of
    ``chunk`` tokens along T (T a multiple of ``chunk``)."""
    b, t, h = la.shape
    return jnp.cumsum(la.reshape(b, t // chunk, chunk, h), axis=2).reshape(b, t, h)


def ssd_chunked_ref(x, dt, A, Bm, Cm, h0, chunk: int = 128):
    """Chunked SSD; same arguments and returns as
    :func:`ssd_sequential_ref`.  T need not divide by ``chunk``: the tail
    is padded with identity steps (``dt = 0``) whose outputs are dropped."""
    b, t, h, p = x.shape
    n = Bm.shape[-1]
    pad = pad_time(t, chunk)
    f = lambda a: jnp.pad(a.astype(F32), [(0, 0), (0, pad)]  # noqa: E731
                          + [(0, 0)] * (a.ndim - 2))
    x, dt, Bm, Cm = f(x), f(dt), f(Bm), f(Cm)
    nc = (t + pad) // chunk
    cs = chunk_cumsum(dt * A, chunk)
    split = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((b, nc, chunk) + a.shape[2:]), 1, 0)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(S, inp):
        xc, dtc, csc, bc, cc = inp               # (Bt, L, ...)
        v = dtc[..., None] * xc                  # (Bt, L, H, P)
        g = jnp.einsum("btn,bsn->bts", cc, bc, precision=HP)
        seg = csc[:, :, None, :] - csc[:, None, :, :]        # (Bt, t, s, H)
        dec = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        y = jnp.einsum("bts,btsh,bshp->bthp", g, dec, v, precision=HP)
        y = y + jnp.exp(csc)[..., None] * jnp.einsum(
            "btn,bhpn->bthp", cc, S, precision=HP)
        last = csc[:, -1]                                    # (Bt, H)
        w = v * jnp.exp(last[:, None, :] - csc)[..., None]
        S = (jnp.exp(last)[..., None, None] * S
             + jnp.einsum("bshp,bsn->bhpn", w, bc, precision=HP))
        return S, y

    S, ys = jax.lax.scan(body, h0.astype(F32),
                         tuple(split(a) for a in (x, dt, cs, Bm, Cm)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * chunk, h, p)
    return y[:, :t], S


def random_inputs(shape, t: int, seed: int = 0, dtype=jnp.bfloat16):
    """Seeded operands ``(x, dt, A, Bm, Cm, h0)`` of ``t`` tokens at
    ``shape = (Bt, H, P, N)``.  dt in [0.05, 3] and |A| in [1, 8]: decays
    from exp(-0.05) to exp(-24) a token; h0 nonzero."""
    b, h, p, n = shape
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((b, t, h, p)), dtype)
    dt = jnp.asarray(rng.uniform(0.05, 3.0, (b, t, h)), F32)
    a = -jnp.asarray(np.geomspace(1.0, 8.0, h), F32)
    bm = jnp.asarray(rng.standard_normal((b, t, n)), dtype)
    cm = jnp.asarray(rng.standard_normal((b, t, n)), dtype)
    h0 = jnp.asarray(rng.standard_normal((b, h, p, n)), F32)
    return x, dt, a, bm, cm, h0


def rel_error(got, want) -> float:
    """The largest absolute difference over the largest absolute value
    of the reference ``want``."""
    return float(jnp.max(jnp.abs(got.astype(F32) - want))
                 / jnp.max(jnp.abs(want)))
