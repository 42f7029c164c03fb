#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metric readers are found by name
from ``BENCHMARK.json`` (see ``bench/spec.py``). Standard error ends with
each compared number beside its limit; the last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` the ``breakdown``), then ``checks``.

``--control 1`` puts the control in the program's place for the check: the
reference computed on residues stored in 4 bits. It must come out not
correct. Without a TPU, or with fewer chips than the cell asks for, the run
exits 3 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The persistent compilation cache stays inside the checkout, at a fixed
    # path; the program's own cache helper takes it from this variable.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # libtpu would otherwise write its logs under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import NoChip, execute

    try:
        out = execute(args.workload, args.seed, args.seconds,
                      bool(args.trace), control=bool(args.control))
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    for line in out["info"]:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
