"""Request latencies of an open-loop window, from due time to
``Ticket.result``. A request that failed or never answered counts as the
window's deadline, so it misses every latency limit."""

from __future__ import annotations

import math


def latencies_ms(ctx) -> list:
    reqs = ctx["window"].get("requests") or []
    return [1e3 * (r["latency_s"] if "latency_s" in r
                   else r["deadline"] - r["t_due"]) for r in reqs]


def quantile(values, q: float) -> float:
    """Nearest-rank quantile of all values."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v) - 1e-9) - 1)]
