"""The program's spans in the window, for the readers of ``program_span``
metrics. ``ctx["spans"]`` holds every span that
started inside the window, as ``Span.to_json()`` plus ``t_end``."""

from __future__ import annotations


def named(ctx, *names) -> list:
    return [s for s in ctx["spans"] if s["name"] in names]


def scan_total_per_residue(ctx, attr: str):
    """Sum of ``attr`` over the window's ``scanner.scan`` spans, per residue
    of the window; None where no scan span carries it."""
    residues = ctx["window"].get("residues")
    vals = [s["attrs"][attr] for s in named(ctx, "scanner.scan")
            if attr in s["attrs"]]
    if not residues or not vals:
        return None
    return sum(vals) / residues
