"""window_ms_per_token: decode-window program (ServeEngine._serve_window)
device time in the traced stretch / (windows x K)."""


def read(ctx):
    times = ctx.trace.program_times("window")
    k = int(ctx.cell.serving["decode_window"])
    return 1e3 * sum(times) / (len(times) * k) if times else None
