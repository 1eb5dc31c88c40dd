"""wkv_roofline: for the WKV kernel calls in the traced stretch, the
least time the chip could take (the larger of FLOPs / peak and bytes /
HBM bandwidth, from each call's operand and result shapes, the state
counted once per call) over their device time."""

from bench import flops


def read(ctx):
    least = spent = 0.0
    for call in ctx.trace.kernel_calls("wkv"):
        f, b = flops.wkv_call_cost(call.operands, call.results)
        least += max(f / ctx.peaks["bf16_flops_per_s"], b / ctx.peaks["hbm_bytes_per_s"])
        spent += call.seconds
    return 100.0 * least / spent if spent > 0 else None
