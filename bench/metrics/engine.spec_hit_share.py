"""Share of the window's speculative chunks whose entry state the profile
predicted: the sum of ``hit_chunks`` over the sum of ``total_chunks`` of
the ``speculative.scan`` spans (scan path) and of the speculative
``scanner.device`` spans with ``path="stream"`` (stream path)."""

from bench.spans import named


def read(ctx):
    spans = named(ctx, "speculative.scan") + [
        s for s in named(ctx, "scanner.device")
        if s["attrs"].get("path") == "stream"
        and s["attrs"].get("mode") == "speculative"]
    total = sum(s["attrs"].get("total_chunks", 0) for s in spans)
    if not total:
        return None
    return 100.0 * sum(s["attrs"].get("hit_chunks", 0) for s in spans) / total
