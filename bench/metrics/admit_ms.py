"""admit_ms: mean device time of one admit program
(ServeEngine._admit_step) in the traced stretch."""


def read(ctx):
    times = ctx.trace.program_times("admit")
    return 1e3 * sum(times) / len(times) if times else None
