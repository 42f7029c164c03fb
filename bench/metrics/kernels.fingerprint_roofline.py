"""The Pallas fingerprint kernel's device time against its least time on
the chip (bytes over peak bandwidth; see ``bench/roofline.py``)."""

from bench.roofline import kernel_share


def read(ctx):
    return kernel_share(ctx, "fingerprint_bank_pallas")
