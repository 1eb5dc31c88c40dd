"""Public op: decayed sequence scan with automatic backend dispatch.

On TPU this runs the Pallas kernel; on CPU (this container) the kernel runs
in interpret mode for validation, while speed-sensitive callers (models)
get a jnp path via ``use_kernel=False`` / auto off-TPU.

The jnp path is itself dispatched per backend (CPU wall-clock, measured
when this dispatch was chosen): the log-depth ``associative_scan`` only
wins where gather-heavy tree steps are cheap (accelerators); on CPU it was
measured *slower* than the plain sequential reference (8.5ms vs 7.0ms at
B=4,T=2048,D=256), and the two-level ``chunked_linear_scan`` schedule is
slower still in XLA-CPU (9.3–12.8ms across chunk sizes and layouts — the
intra-chunk tree pays the same strided-gather tax).  What wins on CPU is
the *linear* scan in chunk-unrolled form — ``lax.scan`` with a small
unroll, so XLA composes consecutive steps into straight-line vector code
(4.6ms, 1.9x over log-depth).  That is the CPU dispatch here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import (
    KernelResources,
    halving_chunk,
    interpret_default,
    on_tpu,
    pick_d_block,
    register_kernel_resources,
    validate_divisible,
)
from repro.kernels.elevator_scan.decode import (
    ELEVATOR_DECODE_WINDOW_MAX,
    elevator_decode_diff,
)
from repro.kernels.elevator_scan.kernel import elevator_scan_pallas
from repro.kernels.elevator_scan.ref import elevator_scan_ref

# lax.scan unroll for the CPU linear path: 2 composed steps per iteration
# was the measured sweet spot (4.6ms vs 5.2–5.3ms at unroll 4/8).
_CPU_SCAN_UNROLL = 2


def elevator_scan_logdepth(a: jax.Array, x: jax.Array, h0=None) -> jax.Array:
    """Log-depth associative-scan form of the recurrence (float32 math).

    Exposed for benchmarks and non-CPU jnp dispatch; models go through
    :func:`elevator_scan`.
    """
    a32, x32 = a.astype(jnp.float32), x.astype(jnp.float32)
    if h0 is not None:
        x32 = x32.at[:, 0].add(a32[:, 0] * h0.astype(jnp.float32))

    def compose(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    _, h = jax.lax.associative_scan(compose, (a32, x32), axis=1)
    return h.astype(x.dtype)


def elevator_scan_linear(a: jax.Array, x: jax.Array, h0=None) -> jax.Array:
    """Linear (sequential) scan, chunk-unrolled for XLA-CPU (float32 math)."""
    b, t, d = x.shape
    a32, x32 = a.astype(jnp.float32), x.astype(jnp.float32)
    init = (
        jnp.zeros((b, d), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    )

    def step(h, inputs):
        at, xt = inputs
        h = at * h + xt
        return h, h

    _, hs = jax.lax.scan(
        step, init, (a32.swapaxes(0, 1), x32.swapaxes(0, 1)),
        unroll=_CPU_SCAN_UNROLL,
    )
    return hs.swapaxes(0, 1).astype(x.dtype)


# NOTE: intentionally un-jitted — called under the model's outer jit; a
# nested jit would cache across the scan_unroll() lowering flag.
def elevator_scan(
    a: jax.Array,
    x: jax.Array,
    h0: jax.Array | None = None,
    *,
    chunk: int = 256,
    use_kernel: bool | None = None,
    decode: bool | None = None,
) -> jax.Array:
    """h[b,t,d] = a[b,t,d] * h[b,t-1,d] + x[b,t,d].

    ``use_kernel=None`` auto-selects: Pallas on TPU, jnp elsewhere — and
    the jnp form is itself backend-dispatched (linear scan on CPU,
    log-depth associative scan otherwise; identical math, validated
    against each other in tests/test_kernel_elevator_scan.py).

    ``decode=True`` marks a *stateful serving* call (threaded from
    ``apply_rglru_block``): windows up to
    :data:`~repro.kernels.elevator_scan.decode.ELEVATOR_DECODE_WINDOW_MAX`
    tokens take the persistent-state decode kernel
    (:mod:`repro.kernels.elevator_scan.decode`) — h is read from HBM once
    and written once per window, intermediate states ride a VMEM carry —
    fixing the old dispatch that forced the jnp path at ``t == 1`` and
    round-tripped h through HBM every generated token.  Longer stateful
    sweeps (cache prefill) fall through to the chunked paths.
    ``decode=None`` infers ``t == 1``.
    """
    kernel = on_tpu() if use_kernel is None else use_kernel
    t = x.shape[1]
    if decode is None:
        decode = t == 1
    if decode and t <= ELEVATOR_DECODE_WINDOW_MAX:
        if kernel:
            return elevator_decode_diff(interpret_default(), True, a, x,
                                        _h0_or_zeros(a, h0))
        # jnp fallback: the sequential scan is the cheapest form for a
        # short stateful window (no chunk structure to exploit).
        return elevator_scan_linear(a, x, h0)
    if kernel:
        c = halving_chunk(t, chunk)
        return elevator_scan_pallas(a, x, h0, chunk=c, interpret=interpret_default())
    if jax.default_backend() == "cpu":
        return elevator_scan_linear(a, x, h0)
    return elevator_scan_logdepth(a, x, h0)


def _h0_or_zeros(a: jax.Array, h0: jax.Array | None) -> jax.Array:
    if h0 is not None:
        return h0
    b, _, d = a.shape
    return jnp.zeros((b, d), jnp.float32)


# --------------------------------------------------------------------------
# Static resource declarations (repro.analysis.resources)
# --------------------------------------------------------------------------

def _elevator_geometry(cfg):
    d = cfg.d_rnn
    d_block = pick_d_block(d)
    isz = jnp.dtype(cfg.dtype).itemsize
    return d, d_block, isz


@register_kernel_resources("elevator_scan.fwd")
def _elevator_fwd_resources(cfg, *, t: int = 4096, chunk: int = 256):
    """Chunked decayed scan (the RG-LRU recurrence)."""
    if "rec" not in tuple(cfg.pattern):
        return None
    d, d_block, isz = _elevator_geometry(cfg)
    c = halving_chunk(t, chunk)
    validate_divisible("T", t, c)
    seq = (1, c, d_block)
    return KernelResources(
        kernel="elevator_scan.fwd",
        location="src/repro/kernels/elevator_scan/kernel.py:elevator_scan_pallas",
        grid=(1, d // d_block, t // c),
        blocks=(
            ("a", seq, isz), ("x", seq, isz),
            ("h0", (1, 1, d_block), 4), ("out", seq, isz),
        ),
        scratch=(("h", (1, d_block), 4),),
    )


@register_kernel_resources("elevator_scan.decode_window")
def _elevator_decode_resources(cfg, *, window: int = ELEVATOR_DECODE_WINDOW_MAX):
    """Persistent-state decode window: the whole window is one block, h
    rides an in-kernel loop across it."""
    if "rec" not in tuple(cfg.pattern):
        return None
    d, d_block, isz = _elevator_geometry(cfg)
    seq = (1, window, d_block)
    return KernelResources(
        kernel="elevator_scan.decode_window",
        location=("src/repro/kernels/elevator_scan/decode.py:"
                  "elevator_decode_window_pallas"),
        grid=(1, d // d_block),
        blocks=(
            ("a", seq, isz), ("x", seq, isz),
            ("h0", (1, 1, d_block), 4), ("out", seq, isz),
        ),
    )
