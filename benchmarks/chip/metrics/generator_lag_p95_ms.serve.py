"""95th percentile of how late the load generator sent each request
after it was due; a starved generator would read as a fast server."""


def read(ctx):
    return ctx.counters.get("generator_lag_p95_ms")
