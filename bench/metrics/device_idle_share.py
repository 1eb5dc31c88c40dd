"""device_idle_share: 1 - (union of device op intervals / traced
window)."""


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - t.busy_s / t.window_s) if t.window_s > 0 else None
