"""Public op: the SSD recurrence with backend dispatch.

``use_kernel=None`` picks the Pallas kernels on TPU and the jnp oracles
elsewhere (``True`` forces the kernels, in interpret mode off-TPU, for
parity tests).  The WKV rule decides the form: a stateful (serving) call
of at most :data:`~repro.kernels.wkv.decode.DECODE_WINDOW_MAX` tokens
takes the decode-window kernel, anything longer the chunk kernel.

``live`` (Bt,) bool marks the rows with a valid token.  The decode-window
kernel neither reads nor writes the others (their state stays
bit-identical, NaN included) and gives them a zero ``y``.  The chunk
kernel and the jnp oracles ignore it: there a row whose ``dt`` is all 0
takes identity steps.
"""

from __future__ import annotations

import jax

from repro.kernels.common import interpret_default, on_tpu
from repro.kernels.ssd.decode import ssd_decode_window_pallas
from repro.kernels.ssd.kernel import CHUNK, ssd_chunk_pallas
from repro.kernels.ssd.ref import ssd_chunked_ref, ssd_sequential_ref
from repro.kernels.wkv.decode import DECODE_WINDOW_MAX


def ssd(x, dt, A, Bm, Cm, h, layer=None, live=None, *, decode: bool,
        use_kernel: bool | None = None):
    """``S_t = exp(A dt_t) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t``.

    x (Bt, T, H, P); dt (Bt, T, H) f32 (0 at a masked token); A (H,) f32;
    Bm, Cm (Bt, T, N); h the entering state (Bt, H, P, N) f32, or with
    ``layer`` a stack (L, Bt, H, P, N) of which layer ``layer`` is read
    and written; ``live`` (Bt,) bool or None (every row), as above.
    Returns (y (Bt, T, H, P) in x.dtype, the state in the form ``h`` came
    in)."""
    kernel = on_tpu() if use_kernel is None else use_kernel
    window = decode and x.shape[1] <= DECODE_WINDOW_MAX
    if kernel:
        # Both kernels update their layer of the stack in place.
        stack, at = (h[None], 0) if layer is None else (h, layer)
        if window:
            y, stack = ssd_decode_window_pallas(
                x, dt, A, Bm, Cm, stack, at, live,
                interpret=interpret_default())
        else:
            y, stack = ssd_chunk_pallas(x, dt, A, Bm, Cm, stack, at,
                                        interpret=interpret_default())
        return y, (stack[0] if layer is None else stack)
    h0 = h if layer is None else jax.lax.dynamic_index_in_dim(
        h, layer, keepdims=False)
    if window:
        y, s = ssd_sequential_ref(x, dt, A, Bm, Cm, h0)
    else:
        y, s = ssd_chunked_ref(x, dt, A, Bm, Cm, h0, chunk=CHUNK)
    if layer is not None:
        s = jax.lax.dynamic_update_index_in_dim(h, s, layer, 0)
    return y.astype(x.dtype), s
