"""step_mfu: model FLOPs of the real tokens of the traced steps
(admitted prompt tokens and emitted tokens; padding and dead slots left
out) over (admit + window program device time) x peak bf16 FLOP/s."""

from bench import flops


def read(ctx):
    t = sum(ctx.trace.program_times("admit")) + sum(ctx.trace.program_times("window"))
    steps = ctx.trace.steps_within(ctx.served.steps)
    work = sum(flops.prompt_flops(ctx.sizes, p) for s in steps for p in s["prompts"])
    work += sum(flops.decode_flops(ctx.sizes, q) for s in steps for q in s["positions"])
    if t <= 0 or work <= 0:
        return None
    return 100.0 * work / (t * ctx.peaks["bf16_flops_per_s"])
