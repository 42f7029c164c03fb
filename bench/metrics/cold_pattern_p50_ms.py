"""Median (nearest rank) latency of requests that carry a never-seen user
pattern, due time to ``Ticket.result`` (host clock)."""

from bench.latency import latencies_ms, quantile


def read(ctx):
    v = latencies_ms(ctx)
    return quantile(v, 0.5) if v else None
