"""The release annotation pipeline of ``corpus_job.py`` over the
configuration's own alphabet: the same closed loop and the same check; only
where a job is made does it differ, passing ``alphabet=cfg["alphabet"]`` to
``ScanService.corpus_job``, so the bank's strings compile over those
letters. A program whose ``corpus_job`` takes no alphabet fails in set-up."""

from __future__ import annotations

import shutil

from bench.drivers import corpus_job


class Driver(corpus_job.Driver):
    def setup(self) -> None:
        from repro.scanservice import CorpusManifest, ScanService

        self.CorpusManifest = CorpusManifest
        self.svc = ScanService(self.out / "store")
        shutil.rmtree(self.out / "jobs", ignore_errors=True)
        warm = self.gen.warmup_docs(self.cfg, self.traffic, self.shard_docs,
                                    self.seed
                                    + corpus_job.WARMUP_SEED_OFFSET)
        self._corpus_job(CorpusManifest.from_docs(warm, self.shard_docs),
                         "warmup").run()
        self.cycles = [self._job(0)]

    def _job(self, cycle: int):
        docs = self.gen.cycle_docs(self.cfg, self.traffic, self.shard_docs,
                                   self.seed, cycle)
        manifest = self.CorpusManifest.from_docs(docs, self.shard_docs)
        return {"job": self._corpus_job(manifest, f"cycle{cycle}"),
                "docs": docs, "manifest": manifest}

    def _corpus_job(self, manifest, name: str):
        return self.svc.corpus_job(self.pats, manifest,
                                   self.out / "jobs" / name,
                                   alphabet=self.cfg["alphabet"])
