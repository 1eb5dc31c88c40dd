"""ttft_p90_ms: 90th percentile over all requests due in the window of
due time -> first token, host clock."""

from bench import latency


def read(ctx):
    return latency.tail_ms(latency.ttft_s(ctx.served), 90)
