"""hybrid_step_mfu: ``step_mfu`` for a model whose layers are of several
kinds: model FLOPs of the real tokens of the traced steps (admitted
prompt tokens and emitted tokens; padding and dead slots left out) over
(admit + window program device time) x peak bf16 FLOP/s.

FLOPs of a token are counted layer by layer, by the layer's kind:

* Mamba-2: the input projection (z, the conv channels, dt), the causal
  conv, the SSD recurrence (5 x heads x head_dim x d_state: decay,
  accumulate, read out) and the output projection;
* attention: the Q, K, V and O projections, and the scores and weighted
  sum over the positions it attends to (itself included);
* the SwiGLU MLP of every layer;

and the vocabulary projection once per emitted token (the serving path
projects only the position it samples).  Multiply-adds count two."""


def layer_flops(sizes: dict, kind: str, context: int) -> float:
    """FLOPs of one token through one layer of ``kind``, attending to
    ``context`` positions."""
    d = sizes["d_model"]
    mlp = 2 * 3 * d * sizes["d_ff"]
    if kind == "mamba":
        h, p, n = (sizes["mamba_heads"], sizes["mamba_head_dim"],
                   sizes["mamba_d_state"])
        di = h * p
        conv = di + 2 * n                  # x, then B and C (one group)
        return (mlp + 2 * d * (di + conv + h) + 2 * sizes["conv_width"] * conv
                + 5 * h * p * n + 2 * di * d)
    hq, hkv, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    proj = 2 * d * hd * (2 * hq + 2 * hkv)
    return mlp + proj + 4 * hq * hd * context


def token_flops(sizes: dict, context: int) -> float:
    """Every layer's FLOPs for one token at ``context`` positions."""
    pattern = sizes["pattern"]
    return float(sum(layer_flops(sizes, pattern[i % len(pattern)], context)
                     for i in range(sizes["num_layers"])))


def head_flops(sizes: dict) -> float:
    return 2.0 * sizes["d_model"] * sizes["vocab_size"]


def prompt_flops(sizes: dict, length: int) -> float:
    """A whole prompt plus the one vocabulary projection that yields its
    first token."""
    return (sum(token_flops(sizes, c) for c in range(1, length + 1))
            + head_flops(sizes))


def decode_flops(sizes: dict, position: int) -> float:
    """One decode step whose input token sits at ``position``."""
    return token_flops(sizes, position + 1) + head_flops(sizes)


def read(ctx):
    t = (sum(ctx.trace.program_times("admit"))
         + sum(ctx.trace.program_times("window")))
    steps = ctx.trace.steps_within(ctx.served.steps)
    work = sum(prompt_flops(ctx.sizes, p) for s in steps for p in s["prompts"])
    work += sum(decode_flops(ctx.sizes, q) for s in steps for q in s["positions"])
    if t <= 0 or work <= 0:
        return None
    return 100.0 * work / (t * ctx.peaks["bf16_flops_per_s"])
