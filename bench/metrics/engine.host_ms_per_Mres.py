"""Host time inside the window's scans, per million residues: the wall of
each ``scanner.scan`` span less the wall of the ``scanner.device`` spans
under it (upload, program, read-back), summed (host clock)."""

from bench.spans import named


def read(ctx):
    residues = ctx["window"].get("residues")
    scans = {s["span_id"]: s for s in named(ctx, "scanner.scan")}
    parent = {s["span_id"]: s["parent_id"] for s in ctx["spans"]}
    device = []
    for s in named(ctx, "scanner.device"):
        p = s["parent_id"]
        while p is not None and p not in scans:
            p = parent.get(p)
        if p is not None:
            device.append(s["wall_s"])
    if not residues or not device:
        return None
    host_s = sum(s["wall_s"] for s in scans.values()) - sum(device)
    return 1e3 * host_s / (residues / 1e6)
