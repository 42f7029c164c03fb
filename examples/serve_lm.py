"""Scan service demo: warm starts + coalesced requests over the PROSITE bank.

    PYTHONPATH=src python examples/serve_lm.py [--store DIR] [--requests 12]

Run it twice: the first run pays SFA construction once and persists every
artifact to the store directory; the second run warm-starts from disk and
compiles the whole bank with **zero construction rounds**. Each run then
fires a burst of small scan requests at the coalescing scheduler — all of
them ride one fused bank compile + scan and are demultiplexed per request,
bit-identical to scanning each request alone.

(This file previously demoed the LM-era continuous-batching engine; that
path still lives in ``repro.serve`` / ``launch/serve.py``. The scan domain
is the repo's north star, so the example now serves scans.)
"""

import argparse
import sys
import tempfile
import time

sys.path.insert(0, "src")

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.prosite import PROSITE_EXTRA, PROSITE_SAMPLES, synthetic_protein
from repro.engine import Scanner

BANK = [pid for pid in {**PROSITE_SAMPLES, **PROSITE_EXTRA}]


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--store", default=None,
                    help="artifact store dir (default: a temp dir — use a "
                         "real path to see the second run warm-start)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--patterns-per-request", type=int, default=3)
    ap.add_argument("--docs-per-request", type=int, default=4)
    args = ap.parse_args()

    store_dir = args.store or tempfile.mkdtemp(prefix="scan-store-")
    rng = np.random.default_rng(0)

    with Scanner.service(store_dir) as svc:
        n = svc.warm_start()
        print(f"store: {store_dir} ({n} artifact(s) preloaded)")

        t0 = time.perf_counter()
        scanner = svc.scanner(BANK)
        dt = time.perf_counter() - t0
        r = scanner.construction_report
        label = "WARM (zero construction rounds)" if r.rounds == 0 else "cold"
        print(f"compiled {scanner.n_patterns} patterns in {dt:.2f}s — {label}: "
              f"{r.rounds} round(s), {r.cache_hits} cache hit(s), "
              f"{r.constructed} built, {r.blown} blown")

        # A burst of overlapping requests: all coalesce into one batch.
        tickets = []
        for i in range(args.requests):
            pats = [str(p) for p in rng.choice(
                BANK, size=args.patterns_per_request, replace=False)]
            docs = [synthetic_protein(240, seed=int(rng.integers(1 << 16)))
                    for _ in range(args.docs_per_request)]
            tickets.append((pats, svc.submit(pats, docs)))
        t0 = time.perf_counter()
        served = svc.flush()
        dt = time.perf_counter() - t0
        stats = svc.scheduler.stats
        print(f"served {served} coalesced request(s) in {dt:.3f}s "
              f"(union: {stats.union_patterns} patterns x "
              f"{stats.union_docs} docs in {stats.flushes} fused scan(s))")
        for pats, t in tickets[:3]:
            res = t.result()
            print(f"  {pats} -> counts {res.counts.tolist()} "
                  f"(rode a batch of {res.batch_size})")

    if not args.store:
        print("tip: pass --store ./scan_store and run twice to see the "
              "warm start")


if __name__ == "__main__":
    main()
