"""Wall of the window's enumeration round trips per million residues of
the window: the ``scanner.device`` spans whose ``mode`` is
``enumeration`` (upload, the enumeration executor, read-back; host
clock)."""

from bench.spans import named


def read(ctx):
    residues = ctx["window"].get("residues")
    walls = [s["wall_s"] for s in named(ctx, "scanner.device")
             if s["attrs"].get("mode") == "enumeration"]
    if not residues or not walls:
        return None
    return 1e3 * sum(walls) / (residues / 1e6)
