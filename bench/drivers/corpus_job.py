"""A release annotation pipeline: ``ScanService.corpus_job(bank, manifest,
workdir).run(max_shards=1)`` in a closed loop until the window is over. The
window closes at the end of the first shard that finishes after
``--seconds``, so every residue counted was scanned inside it. The traffic
file names the generator of the documents (``bench/generators/``)."""

from __future__ import annotations

import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import reference, spec

#: Seeds of warm-up traffic lie far from any seed a run is given.
WARMUP_SEED_OFFSET = 0x5EED_0000_0000


class Driver:
    def __init__(self, c: dict, seed: int, seconds: float, out: Path):
        self.c, self.seed, self.seconds, self.out = c, seed, seconds, out
        self.cfg, self.traffic = c["config"], c["traffic"]
        self.pats = list(self.cfg["bank"].values())
        self.shard_docs = self.cfg["deployment"]["shard_docs"]
        self.gen = spec.generator(self.traffic["generator"],
                                  Path(c["bench"]))

    def setup(self) -> None:
        from repro.scanservice import CorpusManifest, ScanService

        self.CorpusManifest = CorpusManifest
        self.svc = ScanService(self.out / "store")
        shutil.rmtree(self.out / "jobs", ignore_errors=True)
        # Warm-up: every shape a cycle holds, on its own seed, through the
        # same job path, so every (docs, length) program exists.
        warm = self.gen.warmup_docs(self.cfg, self.traffic, self.shard_docs,
                                    self.seed + WARMUP_SEED_OFFSET)
        self.svc.corpus_job(
            self.pats, CorpusManifest.from_docs(warm, self.shard_docs),
            self.out / "jobs" / "warmup").run()
        self.cycles = [self._job(0)]

    def _job(self, cycle: int):
        docs = self.gen.cycle_docs(self.cfg, self.traffic, self.shard_docs,
                                   self.seed, cycle)
        manifest = self.CorpusManifest.from_docs(docs, self.shard_docs)
        job = self.svc.corpus_job(self.pats, manifest,
                                  self.out / "jobs" / f"cycle{cycle}")
        return {"job": job, "docs": docs, "manifest": manifest}

    def window(self) -> dict:
        shards, failed, gen_s = [], 0, 0.0
        t0 = time.perf_counter()
        cycle = 0
        while True:
            cur = self.cycles[cycle]
            job = cur["job"]
            if job.complete:
                g0 = time.perf_counter()
                cycle += 1
                self.cycles.append(self._job(cycle))
                gen_s += time.perf_counter() - g0
                continue
            shard = job.pending()[0]
            try:
                job.run(max_shards=1)
            except Exception as exc:  # a failed shard counts, the loop ends
                failed += 1
                print(f"shard {cycle}/{shard} failed: {exc!r}", file=sys.stderr)
                break
            t = time.perf_counter()
            lo, hi = cur["manifest"].shard_range(shard)
            shards.append({"cycle": cycle, "shard": shard, "t": t - t0,
                           "residues": sum(len(d) for d in cur["docs"][lo:hi])})
            if t - t0 >= self.seconds:
                break
        t_end = time.perf_counter()
        self.shards = shards
        return {"t0": t0, "t_end": t_end, "shards": shards,
                "attempted": len(shards) + failed, "failed": failed,
                "in_window_generation_s": gen_s,
                "residues": sum(s["residues"] for s in shards)}

    def close(self) -> None:
        self.svc.close()

    def check(self, control: bool) -> tuple:
        """-> (compared numbers, info). A sample of the documents of every
        finished shard, drawn from the seed, against the reference."""
        rng = np.random.default_rng(self.seed + 1)
        per = max(1, math.ceil(self.traffic["check_docs"]
                               / max(1, len(self.shards))))
        got, docs = [], []
        missing = 0
        for s in self.shards:
            cur = self.cycles[s["cycle"]]
            lo, hi = cur["manifest"].shard_range(s["shard"])
            path = (self.out / "jobs" / f"cycle{s['cycle']}" / "shards"
                    / f"shard_{s['shard']:05d}.npz")
            try:
                with np.load(path) as z:
                    hits = np.asarray(z["hits"], dtype=bool)
            except Exception:
                missing += 1
                continue
            pick = np.sort(rng.choice(hi - lo, size=min(per, hi - lo),
                                      replace=False))
            got.append(hits[:, pick])
            docs += [cur["docs"][lo + i] for i in pick]
        want = reference.hits(self.pats, docs)
        have = (reference.hits(self.pats, [reference.fold4(d) for d in docs])
                if control else
                (np.concatenate(got, axis=1) if got else want[:, :0]))
        bad = int((have != want).sum()) if have.shape == want.shape \
            else int(want.size)
        return ({"hit_mismatches": bad, "answers_missing": missing},
                {"docs_checked": len(docs), "hits_expected": int(want.sum())})
