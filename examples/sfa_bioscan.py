"""ScanProsite-style bulk scan (paper §IV) on the Scanner engine: the full
bundled signature bank matched over a synthetic protein database in one
batched program — pattern-parallel (the bank axis) × chunk-parallel (the SFA
axis), with ``auto`` mode giving each signature the paper's single-lookup
SFA inner loop when construction fits the budget, a per-pattern census, and
match localization for the hits.

    PYTHONPATH=src python examples/sfa_bioscan.py [--db-size 200] [--len 2000]
        [--mode auto|sfa|enumeration] [--backend xla|pallas|reference]
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import load_bank, synthetic_protein
from repro.engine import ChunkPolicy, ScanPlan, Scanner

N_CHUNKS = 16


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--db-size", type=int, default=200)
    ap.add_argument("--len", dest="length", type=int, default=2000)
    ap.add_argument("--ids", nargs="*", default=None,
                    help="signature ids (default: the full bundled bank)")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "sfa", "enumeration"])
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "pallas", "reference"])
    args = ap.parse_args()

    length = (args.length // N_CHUNKS) * N_CHUNKS
    print(f"building database: {args.db_size} proteins x {length} residues")
    db = [synthetic_protein(length, seed=i) for i in range(args.db_size)]

    t0 = time.perf_counter()
    bank = load_bank(args.ids)
    scanner = Scanner.compile(
        bank,
        ScanPlan(mode=args.mode, backend=args.backend,
                 chunking=ChunkPolicy(n_chunks=N_CHUNKS)),
    )
    t_compile = time.perf_counter() - t0
    n_sfa = sum(1 for m in scanner.pattern_modes.values() if m == "sfa")
    print(f"bank: {bank.n_patterns} signatures, n_max={bank.n_max} states, "
          f"compiled in {t_compile*1e3:.0f} ms "
          f"({n_sfa} SFA-mode / {bank.n_patterns - n_sfa} enumeration)")

    # one batched program: every (pattern, protein, chunk) cell at once
    scanner.scan(db)  # warmup/compile
    t0 = time.perf_counter()
    result = scanner.scan(db)
    counts = result.counts
    t_scan = time.perf_counter() - t0

    chars = args.db_size * length * bank.n_patterns
    print(f"scanned {chars/1e6:.1f} Mchar-pattern in {t_scan:.2f} s "
          f"({chars/t_scan/1e6:.1f} Mchar-pattern/s)")
    print(f"{'id':10s} {'pattern':42s} {'mode':12s} {'hits':>5s}  first match")
    from repro.core.prosite import PROSITE_EXTRA, PROSITE_SAMPLES

    pool = {**PROSITE_SAMPLES, **PROSITE_EXTRA}
    for p, pid in enumerate(scanner.ids):
        first = ""
        hit_rows = np.flatnonzero(result.hits[p])
        if hit_rows.size:
            # localize the first hit with the two-pass position matcher
            i = int(hit_rows[0])
            flags = scanner.locate(db[i], pattern=pid)
            first = f"protein {i} @ {int(np.argmax(flags))}"
        pat = pool.get(pid, "?")
        print(f"{pid:10s} {pat:42s} {scanner.pattern_modes[pid]:12s} "
              f"{int(counts[p]):5d}  {first}")


if __name__ == "__main__":
    main()
