"""attention_roofline: least time of the decode attention the traced
steps needed over the device time of the window program's ops under the
``model.attn.decode`` scope.  The work is that of each emitted token's
decode step over its live positions only: K and V up to its position in
every attention layer, bf16; least time is the larger of FLOPs / peak
and bytes / HBM bandwidth.  It is counted from the positions served,
whatever implementation serves them."""

#: Bytes of one cached K or V element (bf16).
KV_BYTES = 2


def decode_attention_cost(sizes: dict, position: int) -> tuple[float, float]:
    """(FLOPs, bytes) of attending from one token at ``position`` to its
    live positions in every attention layer: scores and weighted sum
    (4 x query heads x head_dim per position), K and V read once per
    position (2 x KV heads x head_dim elements)."""
    pattern = tuple(sizes["pattern"])
    hq, hkv, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    flops = nbytes = 0.0
    for i in range(sizes["num_layers"]):
        kind = pattern[i % len(pattern)]
        if kind in ("attn", "global"):
            live = position + 1
        elif kind == "local":
            live = min(position + 1, sizes["attn_window"])
        else:
            continue
        flops += 4 * hq * hd * live
        nbytes += 2 * hkv * hd * live * KV_BYTES
    return flops, nbytes


def read(ctx):
    scope_time = getattr(ctx.trace, "scope_time", None) or {}
    spent = scope_time.get("window", {}).get("model.attn.decode", 0.0)
    if spent <= 0:
        return None
    flops = nbytes = 0.0
    for s in ctx.trace.steps_within(ctx.served.steps):
        for q in s["positions"]:
            f, b = decode_attention_cost(ctx.sizes, q)
            flops += f
            nbytes += b
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent if least > 0 else None
