"""Profiler trace -> device busy/idle, per-program and per-kernel device time.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX alone. The
window is the host annotation ``bench.window`` that the harness opens around
the measured window; device events are clipped to it.

* busy: the union of the intervals of the device's ``XLA Ops`` events;
* programs: ``XLA Modules`` events summed by program name (the trailing
  ``(<id>)`` dropped);
* ops: ``XLA Ops`` events summed by name;
* kernels: ``XLA Ops`` events whose name or HLO text names a Pallas kernel
  (:data:`KERNELS`), with the bytes each call moves (:mod:`bench.roofline`);
* idle gaps: each gap between busy intervals, charged to the innermost host
  span open at its middle (``no span`` when none is).
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

from .roofline import call_bytes

WINDOW = "bench.window"
_PROGRAM_ID = re.compile(r"\(\d+\)$")
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:
        return {}


def load(path: Path) -> dict:
    """-> {"host": [(name, t0, t1)], "devices": {plane: {line: [(name, t0,
    t1, stats)]}}} with times in ns."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
        elif _DEVICE.match(plane.name):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     _stats(ev) if line.name == "XLA Ops" else {})
                    for ev in line.events]
            devices[plane.name] = lines
    return {"host": host, "devices": devices}


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


#: The Pallas kernels' custom calls as compiled HLO names them: after the
#: jitted wrapper that makes the ``pallas_call`` (``%fingerprint_bank_pallas.1
#: = ... custom-call(...), custom_call_target="tpu_custom_call"``); the
#: kernel function's own name stays inside the serialized Mosaic body.
KERNELS = ("fingerprint_bank_pallas", "expand_bank_pallas",
           "match_bank_chunks_pallas", "compose_pallas")


def kernel_of(name: str, stats: dict) -> str | None:
    """The kernel an ``XLA Ops`` event runs, or None. Only the custom call
    counts: the pads and transposes around it carry the wrapper's name in
    their metadata too."""
    text = str(stats.get("long_name", ""))
    for k in KERNELS:
        if name.split(".")[0] == k or ("custom-call(" in text and k in text):
            return k
    return None


def reduce_loaded(tr: dict) -> dict:
    wins = [(a, b) for n, a, b in tr["host"] if n == WINDOW]
    if not wins:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    lo, hi = wins[-1]
    window_s = (hi - lo) * 1e-9
    busy_total, ops, programs = 0.0, defaultdict(float), defaultdict(float)
    kern = defaultdict(lambda: {"seconds": 0.0, "count": 0, "bytes": 0})
    unions = []
    for lines in tr["devices"].values():
        spans = []
        for name, a, b, st in lines.get("XLA Ops", []):
            a, b = _clip(a, b, lo, hi)
            if b <= a:
                continue
            spans.append((a, b))
            ops[name] += (b - a) * 1e-9
            k = kernel_of(name, st)
            if k is not None:
                kern[k]["seconds"] += (b - a) * 1e-9
                kern[k]["count"] += 1
                kern[k]["bytes"] += call_bytes(str(st.get("long_name") or name))
        for name, a, b, _ in lines.get("XLA Modules", []):
            a, b = _clip(a, b, lo, hi)
            if b > a:
                programs[_PROGRAM_ID.sub("", name)] += (b - a) * 1e-9
        u = _union(spans)
        unions.append(u)
        busy_total += sum(b - a for a, b in u) * 1e-9
    n_dev = max(1, len(tr["devices"]))
    busy_s = busy_total / n_dev

    # Idle gaps of the first device, charged to the innermost host span.
    gaps = defaultdict(float)
    host = sorted((a, b, n) for n, a, b in tr["host"]
                  if n != WINDOW and b > lo and a < hi)
    starts = [h[0] for h in host]
    u = unions[0] if unions else []
    edges = [lo] + [x for iv in u for x in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        name = "no span"
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if host[i][1] >= mid:     # latest-starting span open at mid
                name = host[i][2]
                break
        gaps[name] += (b - a) * 1e-9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:10]]

    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "ops": dict(ops),
        "programs": dict(programs),
        "kernels": {k: dict(v) for k, v in kern.items()},
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(gaps)},
    }


def reduce_trace(path: Path) -> dict:
    return reduce_loaded(load(path))
