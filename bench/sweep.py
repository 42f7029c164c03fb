#!/usr/bin/env python3
"""Find the knee of an open-loop cell: windows at rising offered rates in one
process, after the cell's own set-up.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2,4,8,16

For each rate prints one JSON line: requests sent, failed, latency median
and p95 from the due time, and ``growth``: the median latency of the last
quarter of requests (by due time) over that of the first quarter. A
backlog that grows through the window reads well above 1. The knee is the
highest rate whose backlog does not grow; a cell runs at about four fifths
of it, fixed in its traffic file. Shapes compiled in one window stay
compiled for the next, so later windows see fewer compiles than a fresh
run: the sweep reads the knee a little high.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def summary(reqs: list, rate: float) -> dict:
    from bench.latency import quantile

    lat = [1e3 * (r["latency_s"] if "latency_s" in r
                  else r["deadline"] - r["t_due"]) for r in reqs]
    q = max(1, len(lat) // 4)
    return {
        "rate_per_s": rate, "sent": len(reqs),
        "failed": sum(1 for r in reqs if "result" not in r),
        "p50_ms": quantile(lat, 0.5) if lat else None,
        "p95_ms": quantile(lat, 0.95) if lat else None,
        "growth": (statistics.median(lat[-q:]) / statistics.median(lat[:q])
                   if lat else None),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spec
    from repro.compile_cache import enable_compile_cache

    c = spec.cell(args.workload)
    try:
        harness.require_chips(c["workload"]["chips"])
    except harness.NoChip as exc:
        print(f"sweep: {exc}", file=sys.stderr)
        return 3
    enable_compile_cache()
    out = harness.OUT / args.workload
    out.mkdir(parents=True, exist_ok=True)
    driver = spec.driver(c["config"]["deployment"]["kind"])(
        c, args.seed, args.seconds, out)
    driver.setup()
    harness.persistent_cache(False)
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            driver.traffic["rate_per_s"] = rate
            w = driver.offer(args.seed + 1 + k, args.seconds)
            print(json.dumps(summary(w["requests"], rate)), flush=True)
    finally:
        harness.persistent_cache(True)
        driver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
