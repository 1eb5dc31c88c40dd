"""tpot_p90_ms: 90th percentile over all requests due in the window of
(last token - first token) / (tokens - 1); an unfinished request is a
miss."""

from bench import latency


def read(ctx):
    return latency.tail_ms(latency.tpot_s(ctx.served), 90)
