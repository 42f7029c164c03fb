"""Device round trips of the window's streams per million residues of the
window: the ``scanner.device`` spans whose ``path`` is ``stream`` (one per
block and pattern group, and one per speculative fallback)."""

from bench.spans import named


def read(ctx):
    residues = ctx["window"].get("residues")
    trips = [s for s in named(ctx, "scanner.device")
             if s["attrs"].get("path") == "stream"]
    if not residues or not trips:
        return None
    return len(trips) / (residues / 1e6)
