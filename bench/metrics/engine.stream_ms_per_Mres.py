"""Wall of the window's streamed documents per million residues of the
window: the ``scanner.stream`` spans (each one document's block round
trips and its sequential tail; host clock)."""

from bench.spans import named


def read(ctx):
    residues = ctx["window"].get("residues")
    walls = [s["wall_s"] for s in named(ctx, "scanner.stream")]
    if not residues or not walls:
        return None
    return 1e3 * sum(walls) / (residues / 1e6)
