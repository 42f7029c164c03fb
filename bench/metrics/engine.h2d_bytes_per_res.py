"""Bytes the scans in the window sent to the device (the ``h2d_bytes`` of
their ``scanner.scan`` spans, which the ``engine.h2d_bytes`` counter moves
by too), per residue scanned."""

from bench.spans import scan_total_per_residue


def read(ctx):
    return scan_total_per_residue(ctx, "h2d_bytes")
