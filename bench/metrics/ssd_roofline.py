"""ssd_roofline: least time of the SSD (Mamba-2) work the traced steps
needed over the device time of the SSD kernels' ops
(``ssd_chunk_pallas``, ``ssd_decode_window_pallas``).

The work is counted from the real tokens served, whatever implementation
serves them, in every Mamba layer:

* an emitted token: the recurrence's state read and written once in f32,
  its x, B, C, dt and y, and 5 x heads x head_dim x d_state FLOPs (decay
  and accumulate the state, 3; read it out through C, 2);
* an admitted prompt: the chunked form over chunks of :data:`CHUNK`
  tokens (C B^T, then per head the masked (L, L) matrix on dt x, the
  entering state read through C and the exit state written), its
  inputs and outputs once, and the state written once.

Least time is the larger of FLOPs / peak and bytes / HBM bandwidth, for
the decode tokens and for the prompts of each step apart.  With no SSD
op in the trace (a model without Mamba layers, or a program without the
kernels) it reads nothing."""

#: Tokens per chunk of the chunked form.
CHUNK = 128
#: Bytes of an element: activations bf16, the state and dt f32.
ACT, F32 = 2, 4
OPS = ("ssd_chunk_pallas", "ssd_decode_window_pallas")


def _widths(sizes):
    kinds = [sizes["pattern"][i % len(sizes["pattern"])]
             for i in range(sizes["num_layers"])]
    return (kinds.count("mamba"), sizes["mamba_heads"],
            sizes["mamba_head_dim"], sizes["mamba_d_state"])


def token_cost(sizes) -> tuple[float, float]:
    """(FLOPs, bytes) of one emitted token's recurrence in every Mamba
    layer."""
    layers, h, p, n = _widths(sizes)
    state = h * p * n
    io = h * p * ACT * 2 + 2 * n * ACT + h * F32      # x, y; B, C; dt
    return float(layers * 5 * state), float(layers * (2 * state * F32 + io))


def prompt_cost(sizes, length: int) -> tuple[float, float]:
    """(FLOPs, bytes) of a ``length``-token prompt through the chunked
    form in every Mamba layer, from a zero state."""
    layers, h, p, n = _widths(sizes)
    flops = 0.0
    for start in range(0, length, CHUNK):
        l = min(CHUNK, length - start)
        flops += 2 * l * l * n + h * (2 * l * l * p + 4 * l * n * p)
    io = length * (h * p * ACT * 2 + 2 * n * ACT + h * F32)
    return layers * flops, float(layers * (io + h * p * n * F32))


def read(ctx):
    op_time = getattr(ctx.trace, "op_time", None) or {}
    spent = sum(op_time.get(op, 0.0) for op in OPS)
    if spent <= 0 or "mamba_heads" not in ctx.sizes:
        return None
    peak, bw = ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    least = 0.0
    for s in ctx.trace.steps_within(ctx.served.steps):
        f, b = token_cost(ctx.sizes)
        k = len(s["positions"])
        least += max(k * f / peak, k * b / bw)
        costs = [prompt_cost(ctx.sizes, q) for q in s["prompts"]]
        least += max(sum(c[0] for c in costs) / peak,
                     sum(c[1] for c in costs) / bw)
    return 100.0 * least / spent if least > 0 else None
