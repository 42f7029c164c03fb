"""ScanProsite as a web service: an open loop submits the mix's requests at
their due times through ``ScanService.submit``, and a collector thread waits
on each ``Ticket.result``. Latency runs from the due time. The traffic file
names the generator of the requests (``bench/generators/``)."""

from __future__ import annotations

import queue
import shutil
import statistics
import threading
import time
from pathlib import Path

import numpy as np

from bench import data, reference, spec
from bench.drivers.corpus_job import WARMUP_SEED_OFFSET


def open_loop(svc, reqs: list, seconds: float, grace_s: float) -> dict:
    """Submit each request at its due time until ``seconds``; a collector
    thread waits on the tickets in order (the scheduler serves them FIFO)."""
    q: queue.Queue = queue.Queue()

    def collect():
        while True:
            r = q.get()
            if r is None:
                return
            try:
                r["result"] = r["ticket"].result(
                    timeout=max(0.0, r["deadline"] - time.perf_counter()))
                r["done"] = time.perf_counter()
            except Exception as exc:
                r["error"] = repr(exc)

    th = threading.Thread(target=collect, name="bench-collector", daemon=True)
    th.start()
    sent = []
    t0 = time.perf_counter()
    deadline = t0 + seconds + grace_s
    for r in reqs:
        if r["due"] >= seconds:
            break
        wait = t0 + r["due"] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        r["sent"] = time.perf_counter()
        r["deadline"] = deadline
        try:
            r["ticket"] = svc.submit(r["patterns"], r["docs"])
        except Exception as exc:
            r["error"] = repr(exc)
            sent.append(r)
            continue
        q.put(r)
        sent.append(r)
    q.put(None)
    th.join()
    t_end = time.perf_counter()
    for r in sent:
        r["t_due"] = t0 + r["due"]
        if "done" in r:
            r["latency_s"] = r["done"] - r["t_due"]
    return {"t0": t0, "t_end": t_end, "requests": sent}


class Driver:
    def __init__(self, c: dict, seed: int, seconds: float, out: Path):
        self.c, self.seed, self.seconds, self.out = c, seed, seconds, out
        self.cfg, self.traffic = c["config"], c["traffic"]
        self.dep = self.cfg["deployment"]
        self.gen = spec.generator(self.traffic["generator"],
                                  Path(c["bench"]))
        self.info = [data.clip_info(self.cfg["sequences"])]

    def setup(self) -> None:
        from repro.scanservice import ScanService

        store = self.out / "store"
        if self.traffic.get("fresh_store"):
            shutil.rmtree(store, ignore_errors=True)
        self.svc = ScanService(store, driver="thread",
                               window_s=self.dep["window_s"],
                               max_batch=self.dep["max_batch"])
        self.seen = set(self.cfg["bank"].values())
        if not self.traffic.get("fresh_store"):
            self.svc.scanner(list(self.cfg["bank"].values()))  # via the store
        warm_s = self.traffic["warmup_seconds"]
        if warm_s:
            self.offer(self.seed + WARMUP_SEED_OFFSET, warm_s)
        self.reqs = self.gen.requests(self.cfg, self.traffic, self.seed,
                                      self.seconds, self.seen)

    def offer(self, seed: int, seconds: float) -> dict:
        """One open-loop stream of the mix on ``seed`` for ``seconds``
        (warm-up, or a step of a knee sweep)."""
        reqs = self.gen.requests(self.cfg, self.traffic, seed, seconds,
                                 self.seen)
        return open_loop(self.svc, reqs, seconds,
                         self.traffic["grace_seconds"])

    def window(self) -> dict:
        st0 = self.svc.scheduler.stats
        w = open_loop(self.svc, self.reqs, self.seconds,
                      self.traffic["grace_seconds"])
        st1 = self.svc.scheduler.stats
        w["scheduler"] = {k: getattr(st1, k) - getattr(st0, k)
                          for k in ("requests", "flushes", "union_patterns",
                                    "union_docs", "scanner_memo_hits")}
        self.sent = w["requests"]
        failed = sum(1 for r in self.sent if "result" not in r)
        w.update(attempted=len(self.sent), failed=failed)
        late = [r["sent"] - r["t_due"] for r in self.sent]
        w["generator_late_s"] = {
            "median": statistics.median(late) if late else 0.0,
            "max": max(late) if late else 0.0}
        w["info"] = self.info
        return w

    def close(self) -> None:
        self.svc.close()

    def check(self, control: bool) -> tuple:
        """Every request's demuxed slice against the reference; under
        ``control`` the reference on the configuration's control fold in the
        program's place."""
        bad = missing = n_docs = expected = 0
        for r in self.sent:
            want = reference.hits(r["reference"], r["docs"])
            expected += int(want.sum())
            n_docs += len(r["docs"])
            if control:
                have = reference.hits(
                    r["reference"],
                    [reference.control(self.cfg, d) for d in r["docs"]])
            elif "result" in r:
                have = np.asarray(r["result"].hits, dtype=bool)
            else:
                missing += 1
                continue
            bad += int((have != want).sum()) if have.shape == want.shape \
                else int(want.size)
        info = {"requests_checked": len(self.sent), "docs_checked": n_docs,
                "hits_expected": expected}
        if control:
            info["control"] = self.cfg["control"]["what"]
        return {"hit_mismatches": bad, "answers_missing": missing}, info
