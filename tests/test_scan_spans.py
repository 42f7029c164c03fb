"""Spans and transfer counters of the batch scan path.

A corpus job's shard opens ``jobs.shard``, which holds ``scanner.scan`` and
``jobs.checkpoint``; ``scanner.scan`` holds ``scanner.encode``, one
``scanner.stack`` per length and one ``scanner.device`` / ``scanner.tail`` /
``scanner.select`` per length and pattern group. Between shards the job
opens ``jobs.pending`` and ``flight.record``. Every span is per call or per
group, never per document, and the byte counts are those of the arrays
sent and read back. A flight record is written only when something moved:
probes and idle ticks alone write nothing.
"""

from collections import Counter

import numpy as np
import pytest

from repro import obs
from repro.construction import SFACache
from repro.core.prosite import PROSITE_EXTRA, PROSITE_SAMPLES, \
    synthetic_protein
from repro.engine import ConstructionPolicy, ScanPlan, Scanner
from repro.obs.flight import FlightRecorder, read_flight
from repro.scanservice import CorpusJob, CorpusManifest

BANK = list(PROSITE_SAMPLES) + list(PROSITE_EXTRA)
SCAN_CHILDREN = {"scanner.encode", "scanner.stack", "scanner.device",
                 "scanner.tail", "scanner.select"}
#: Neither length is a multiple of ``n_chunks``, so every group has a tail.
LENGTHS = (45, 61)


@pytest.fixture(autouse=True)
def obs_enabled():
    obs.enable()
    yield
    obs.enable()


@pytest.fixture(scope="module")
def cache():
    return SFACache()


def _plan(cache):
    return ScanPlan(construction=ConstructionPolicy(cache=cache))


@pytest.fixture(scope="module")
def scanner(cache):
    return Scanner.compile(BANK, _plan(cache))


def _docs(n):
    return [synthetic_protein(LENGTHS[i % len(LENGTHS)], seed=i)
            for i in range(n)]


def _mark() -> int:
    return max((s.span_id for s in obs.recent_spans(1 << 20)), default=0)


def _spans_after(mark: int) -> list:
    return [s for s in obs.recent_spans(1 << 20) if s.span_id > mark]


def test_corpus_job_emits_the_span_tree(tmp_path, cache):
    job = CorpusJob(BANK, CorpusManifest.from_docs(_docs(8), shard_docs=4),
                    tmp_path / "job", plan=_plan(cache))
    mark = _mark()
    assert job.run().complete
    spans = _spans_after(mark)
    assert {s.trace_id for s in spans} == {job.trace_id}
    by_id = {s.span_id: s for s in spans}

    def parent(s):
        return None if s.parent_id is None else by_id[s.parent_id].name

    for s in spans:
        if s.name in SCAN_CHILDREN:
            assert parent(s) == "scanner.scan", s
        elif s.name in ("scanner.scan", "jobs.checkpoint"):
            assert parent(s) == "jobs.shard", s
        else:
            assert s.name in ("jobs.shard", "jobs.pending", "flight.record")
            assert parent(s) is None, s
    names = Counter(s.name for s in spans)
    shards = job.manifest.n_shards
    assert SCAN_CHILDREN <= set(names)
    assert names["jobs.shard"] == names["scanner.scan"] == shards
    assert names["jobs.checkpoint"] == shards
    # One record per shard: the pre-run record saw only the probe and skipped.
    assert names["flight.record"] == shards
    assert names["jobs.pending"] == 1


def test_probes_between_shards_write_no_pre_run_record(tmp_path, cache):
    """A loop of ``run(max_shards=1)`` with probes in between writes one
    flight record per shard, and the probes' spans go out with it."""
    job = CorpusJob(BANK, CorpusManifest.from_docs(_docs(12), shard_docs=4),
                    tmp_path / "job", plan=_plan(cache))
    while not job.complete:
        job.pending()
        job.run(max_shards=1)
    recs = job.flight_records()
    flights = [r for r in recs if r["kind"] == "flight"]
    assert [r.get("shard") for r in flights] == [0, 1, 2]
    spans = Counter(r["name"] for r in recs if r["kind"] == "span")
    assert spans["jobs.shard"] == 3
    # complete, pending() and run() probe before each shard; the probes
    # after the last record, and its own flight.record span, are not written
    assert spans["jobs.pending"] == 3 * 3
    assert spans["flight.record"] == 2


def test_idle_flight_ticks_write_and_span_nothing(tmp_path):
    fr = FlightRecorder(tmp_path / "flight.jsonl")
    obs.counter("t.scanspans.flight").inc()
    assert fr.record(force=False) is not None
    mark = _mark()
    for _ in range(3):
        assert fr.record(force=False) is None
    assert _spans_after(mark) == []
    assert len(read_flight(tmp_path / "flight.jsonl")) == 1


def test_span_count_does_not_grow_with_the_documents(scanner):
    counts = []
    for n in (8, 64):
        mark = _mark()
        scanner.scan(_docs(n))
        counts.append(Counter(s.name for s in _spans_after(mark)))
    assert counts[0] == counts[1]
    per_group = len(LENGTHS) * len(scanner.groups)
    assert counts[0] == {"scanner.scan": 1, "scanner.encode": 1,
                         "scanner.stack": len(LENGTHS),
                         "scanner.device": per_group,
                         "scanner.tail": per_group,
                         "scanner.select": per_group}


def test_transfer_bytes_are_the_shapes_sent_and_read_back(scanner):
    docs = _docs(24)
    n_chunks = scanner.plan.chunking.n_chunks
    h2d = d2h = 0
    for L, D in Counter(len(d) for d in docs).items():
        for g in scanner.groups:
            h2d += D * (L - L % n_chunks) * 4
            d2h += len(g.indices) * D * g.n * 4
    residues = sum(len(d) for d in docs)
    before = obs.snapshot("engine")
    mark = _mark()
    scanner.scan(docs)
    moved = obs.snapshot_delta(before, obs.snapshot("engine"))
    spans = _spans_after(mark)

    (scan,) = [s for s in spans if s.name == "scanner.scan"]
    assert scan.attrs["h2d_bytes"] == h2d
    assert scan.attrs["d2h_bytes"] == d2h
    assert scan.attrs["residues"] == residues
    assert scan.attrs["docs"] == len(docs)
    device = [s for s in spans if s.name == "scanner.device"]
    assert sum(s.attrs["h2d_bytes"] for s in device) == h2d
    assert sum(s.attrs["d2h_bytes"] for s in device) == d2h
    assert {s.attrs["mode"] for s in device} == {g.mode
                                                 for g in scanner.groups}
    assert moved["engine.h2d_bytes"] == h2d
    assert moved["engine.d2h_bytes"] == d2h
    assert moved["engine.residues_scanned"] == residues


def test_enumeration_round_trips_count_their_pattern_residues(scanner):
    """Each enumeration ``scanner.device`` span carries ``pattern_residues``
    = patterns x docs x head length, and ``engine.enum_pattern_residues``
    moves by the sum; the other modes' spans carry none."""
    docs = _docs(24)
    n_chunks = scanner.plan.chunking.n_chunks
    want = sum(len(g.indices) * D * (L - L % n_chunks)
               for L, D in Counter(len(d) for d in docs).items()
               for g in scanner.groups if g.mode == "enumeration")
    assert want > 0
    before = obs.snapshot("engine")
    mark = _mark()
    scanner.scan(docs)
    moved = obs.snapshot_delta(before, obs.snapshot("engine"))
    device = [s for s in _spans_after(mark) if s.name == "scanner.device"]
    enum = [s for s in device if s.attrs["mode"] == "enumeration"]
    assert len(enum) == len(LENGTHS) * sum(g.mode == "enumeration"
                                           for g in scanner.groups)
    assert sum(s.attrs["pattern_residues"] for s in enum) == want
    assert all(s.attrs["pattern_residues"]
               == s.attrs["patterns"] * s.attrs["docs"] * s.attrs["length"]
               for s in enum)
    assert not [s for s in device
                if s.attrs["mode"] != "enumeration"
                and "pattern_residues" in s.attrs]
    assert moved["engine.enum_pattern_residues"] == want


def test_census_windows_counts_both_round_trips(scanner):
    n_chunks = scanner.plan.chunking.n_chunks
    stride, window = 2 * n_chunks, 4 * n_chunks
    seq = synthetic_protein(10 * stride + 3, seed=7)
    W = (len(seq) - window) // stride + 1
    B = W + window // stride - 1
    h2d = d2h = 0
    for g in scanner.groups:
        Pg = len(g.indices)
        h2d += B * stride * 4 + Pg * B * g.n * 4     # blocks, block maps
        d2h += Pg * B * g.n * 4 + Pg * W * g.n * 4   # block maps, windows
    before = obs.snapshot("engine")
    mark = _mark()
    scanner.census_windows(seq, window, stride)
    moved = obs.snapshot_delta(before, obs.snapshot("engine"))
    device = [s for s in _spans_after(mark) if s.name == "scanner.device"]
    assert len(device) == 2 * len(scanner.groups)
    assert sum(s.attrs["h2d_bytes"] for s in device) == h2d
    assert sum(s.attrs["d2h_bytes"] for s in device) == d2h
    assert moved["engine.h2d_bytes"] == h2d
    assert moved["engine.d2h_bytes"] == d2h


def test_disabled_obs_gives_the_same_hits_and_records_nothing(tmp_path,
                                                              cache, scanner):
    docs = _docs(16)
    manifest = CorpusManifest.from_docs(docs, shard_docs=8)
    want = scanner.scan(docs).hits
    on = CorpusJob(BANK, manifest, tmp_path / "on", plan=_plan(cache))
    on.run()

    before = obs.snapshot()
    mark = _mark()
    obs.disable()
    try:
        got = scanner.scan(docs).hits
        off = CorpusJob(BANK, manifest, tmp_path / "off", plan=_plan(cache))
        off.run()
    finally:
        obs.enable()
    assert np.array_equal(got, want)
    assert np.array_equal(off.aggregate().hits, on.aggregate().hits)
    assert np.array_equal(off.aggregate().hits, want)
    assert _spans_after(mark) == []
    assert obs.snapshot() == before
