"""95th percentile (nearest rank) of every request's latency, due time to
``Ticket.result`` (host clock)."""

from bench.latency import latencies_ms, quantile


def read(ctx):
    v = latencies_ms(ctx)
    return quantile(v, 0.95) if v else None
