"""Least time of a kernel on the chip, from the bytes its call moves.

A kernel's work is counted from the shapes of its call as the trace's HLO
text gives them: every operand read once and the result written once. That
is what the algorithm needs whatever implements it: for the expansion, the
frontier states and the table read and the successors written; for the
fingerprint, the words hashed, their fold constants and the fingerprints
written. The extra FLOPs of a one-hot MXU product are not work, and v5e
publishes no peak for int32 VPU operations, so the least time is bytes over
peak HBM bandwidth alone: the share is bound by bandwidth.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

PEAKS = Path(__file__).resolve().with_name("peaks.json")

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_BYTES) + r")\[([\d,]*)\]")
_CALL = re.compile(r"=\s*(.*?)\s*custom-call\(([^)]*)\)")


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; unknown kinds are an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def hlo_bytes(text: str) -> int:
    """Bytes of every array shape in ``text``."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _BYTES[dtype]
    return total


def call_bytes(text: str) -> int:
    """Bytes one call moves, from its HLO instruction text: the result
    written once and every operand read once. Compiled HLO names a custom
    call's operands without their shapes and gives those shapes in
    ``operand_layout_constraints``; otherwise they stand in the argument
    list. Text that is no call counts every shape in it."""
    m = _CALL.search(text)
    if m is None:
        return hlo_bytes(text)
    result, args = m.group(1), m.group(2)
    operands = hlo_bytes(args)
    if not operands and "operand_layout_constraints=" in text:
        tail = text.split("operand_layout_constraints=", 1)[1]
        operands = hlo_bytes(tail.split("=", 1)[0])
    return hlo_bytes(result) + operands


def kernel_share(ctx, kernel: str):
    """Percent of the kernel's least time over its device time, or None
    when the trace holds no call of it."""
    tr = ctx["trace"]
    if tr is None:
        return None
    k = tr["kernels"].get(kernel)
    if not k or not k["seconds"] or not k["bytes"]:
        return None
    bw = peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (k["bytes"] / bw) / k["seconds"]
