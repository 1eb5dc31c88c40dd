"""queue_wait_p90_ms: 90th percentile of due time -> start of the step
that admitted the request (ServeSession._admit), host clock, over all
requests due in the window."""

from bench import latency


def read(ctx):
    return latency.tail_ms(latency.queue_wait_s(ctx.served), 90)
