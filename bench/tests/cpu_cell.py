"""Drive whole runs of a cell on the CPU at a small size, skipping the look
for a chip, with the timed path sound, broken, or replaced by the control.

    JAX_PLATFORMS=cpu python bench/tests/cpu_cell.py --cell <name> \\
        --out <dir> [--root <checkout>] [--cases sound,control,...]

Prints one JSON object {case: result line}. The faults break
``Scanner.scan``, which every cell's timed path goes through (``scan_shard``
in a corpus job, the coalesced flush in the service):

* ``answer_altered``: one hit of every scan flipped where it is produced;
* ``half_batch``: only the first half of each scan's documents scanned, the
  rest answered "no hit";
* ``state_unchanged``: every automaton left in its start state (no hits).

A cell on one chip has no exchange between chips to leave out.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from contextlib import contextmanager
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve()
sys.path[:0] = [str(HERE.parents[2]), str(HERE.parents[2] / "src")]

FAULTS = ("answer_altered", "half_batch", "state_unchanged")


def small(c: dict) -> None:
    """The cell at a size a test run holds: lengths up to 640, short
    windows, low rates."""
    p = c["config"]["proteins"]
    p["clip"] = [20, 640]
    p["class_edges"] = [20, 40, 80, 160, 320, 640]
    if c["config"]["deployment"]["kind"] == "corpus_job":
        c["config"]["deployment"]["shard_docs"] = 16
        c["traffic"].update(cycle_shards=6, check_docs=64)
    else:
        c["traffic"].update(warmup_seconds=1, grace_seconds=120)
        c["traffic"]["rate_per_s"] = (
            3.0 if c["traffic"]["patterns"]["kind"] == "variant" else 4.0)


@contextmanager
def broken(fault: str | None):
    from repro.engine import scanner as S

    orig = S.Scanner.scan

    def scan(self, docs):
        docs = list(docs)
        half = (len(docs) + 1) // 2 if fault == "half_batch" else len(docs)
        res = orig(self, docs[:half])
        hits = res.hits.copy()
        if fault == "half_batch":
            hits = np.zeros((hits.shape[0], len(docs)), dtype=bool)
            hits[:, :half] = res.hits
        elif fault == "answer_altered":
            hits[0, 0] = not hits[0, 0]
        elif fault == "state_unchanged":
            hits[:] = False
        return dataclasses.replace(res, hits=hits)

    if fault is not None:
        S.Scanner.scan = scan
    try:
        yield
    finally:
        S.Scanner.scan = orig


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", default=str(HERE.parents[2]))
    ap.add_argument("--cases", default="sound,control," + ",".join(FAULTS))
    ap.add_argument("--seed", type=int, default=3_000_000_019)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    from bench.harness import execute

    results = {}
    for case in args.cases.split(","):
        with broken(case if case in FAULTS else None):
            out = execute(args.cell, args.seed, args.seconds, False,
                          control=case == "control", require_chip=False,
                          root=Path(args.root), out_root=Path(args.out),
                          overrides=small)
        results[case] = out["result"]
    print(json.dumps(results))


if __name__ == "__main__":
    main()
