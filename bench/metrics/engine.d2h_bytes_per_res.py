"""Bytes the scans in the window read back from the device (the
``d2h_bytes`` of their ``scanner.scan`` spans, which the
``engine.d2h_bytes`` counter moves by too), per residue scanned."""

from bench.spans import scan_total_per_residue


def read(ctx):
    return scan_total_per_residue(ctx, "d2h_bytes")
