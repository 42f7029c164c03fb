"""Spans and counters of the streaming path.

``Scanner.stream`` opens one ``scanner.stream`` span per input (attributes
``residues``, ``blocks``, ``h2d_bytes``, ``d2h_bytes``); inside it every
full block makes one ``scanner.device`` round trip per pattern group, with
``path="stream"``, and the ragged tail runs under ``scanner.tail``. A
corpus job's streamed documents sit under its ``jobs.shard``. The counters
``engine.stream_blocks`` / ``engine.stream_residues`` move by the stream's
blocks and symbols, and speculative blocks move ``speculative.*``.
"""

from collections import Counter

import numpy as np
import pytest

from repro import obs
from repro.construction import SFACache
from repro.engine import ChunkPolicy, ConstructionPolicy, ScanPlan, Scanner
from repro.scanservice import CorpusJob, CorpusManifest

#: One DNA row per tier: EcoRI (SFA), BglI (enumeration), BslI
#: (speculative), and BsaI's strand pair (SFA).
BANK = {"EcoRI": "G-A-A-T-T-C", "BglI": "G-C-C-x(5)-G-G-C",
        "BslI": "C-C-x(7)-G-G", "BsaI": "G-G-T-C-T-C",
        "BsaI.rc": "G-A-G-A-C-C"}
N_CHUNKS, BLOCK_LEN = 8, 16
BLOCK = N_CHUNKS * BLOCK_LEN


@pytest.fixture(autouse=True)
def obs_enabled():
    obs.enable()
    yield
    obs.enable()


def _plan():
    return ScanPlan(chunking=ChunkPolicy(n_chunks=N_CHUNKS,
                                         block_len=BLOCK_LEN),
                    construction=ConstructionPolicy(cache=SFACache()))


@pytest.fixture(scope="module")
def scanner():
    return Scanner.compile(BANK, _plan(), alphabet="ACGT")


def _doc(length: int, seed: int) -> str:
    rng = np.random.default_rng(seed)
    return "".join(np.array(list("ACGT"))[rng.integers(0, 4, size=length)])


def _mark() -> int:
    return max((s.span_id for s in obs.recent_spans(1 << 20)), default=0)


def _spans_after(mark: int) -> list:
    return [s for s in obs.recent_spans(1 << 20) if s.span_id > mark]


def test_a_stream_makes_one_round_trip_per_block_and_group(scanner):
    assert {g.mode for g in scanner.groups} == {"sfa", "enumeration",
                                                "speculative"}
    blocks, tail = 5, 37
    doc = _doc(blocks * BLOCK + tail, seed=1)
    before = obs.snapshot()
    mark = _mark()
    res = scanner.stream([doc[:300], doc[300:]])
    moved = obs.snapshot_delta(before, obs.snapshot())
    spans = _spans_after(mark)
    by_id = {s.span_id: s for s in spans}
    (stream,) = [s for s in spans if s.name == "scanner.stream"]
    device = [s for s in spans if s.name == "scanner.device"]
    fallbacks = sum(s.name == "speculative.fallback" for s in spans)
    assert len(device) == blocks * len(scanner.groups) + fallbacks
    assert all(s.attrs["path"] == "stream" and s.attrs["docs"] == 1
               and s.attrs["length"] == BLOCK for s in device)
    assert Counter(s.attrs["mode"] for s in device
                   if by_id[s.parent_id].name == "scanner.stream") == \
        Counter({g.mode: blocks for g in scanner.groups})
    (tail_span,) = [s for s in spans if s.name == "scanner.tail"]
    assert tail_span.attrs["symbols"] == tail

    def under_stream(s):
        while s.parent_id in by_id:
            s = by_id[s.parent_id]
            if s.name == "scanner.stream":
                return True
        return False

    assert all(under_stream(s) for s in device + [tail_span])
    assert stream.attrs["residues"] == len(doc) == res.n_symbols
    assert stream.attrs["blocks"] == blocks
    assert stream.attrs["h2d_bytes"] == sum(s.attrs["h2d_bytes"]
                                            for s in device)
    assert stream.attrs["d2h_bytes"] == sum(s.attrs["d2h_bytes"]
                                            for s in device)
    assert moved["engine.stream_blocks"] == blocks
    assert moved["engine.stream_residues"] == len(doc)
    assert moved["engine.h2d_bytes"] == stream.attrs["h2d_bytes"]


def test_speculative_blocks_move_the_speculation_counters(scanner):
    blocks = 3
    spec = [g for g in scanner.groups if g.mode == "speculative"]
    chunks = blocks * N_CHUNKS * sum(len(g.indices) for g in spec)
    before = obs.snapshot("speculative")
    mark = _mark()
    res = scanner.stream([_doc(blocks * BLOCK, seed=2)])
    moved = obs.snapshot_delta(before, obs.snapshot("speculative"))
    assert moved["speculative.total_chunks"] == chunks
    assert res.speculation.total_chunks == chunks
    device = [s for s in _spans_after(mark) if s.name == "scanner.device"
              and s.attrs["mode"] == "speculative"]
    assert sum(s.attrs["total_chunks"] for s in device) == chunks
    assert sum(s.attrs["hit_chunks"] for s in device) == \
        moved.get("speculative.hit_chunks", 0)


def test_speculative_scan_spans_carry_their_chunk_counts(scanner):
    mark = _mark()
    res = scanner.scan([_doc(3 * BLOCK, seed=s) for s in range(4)])
    (sp,) = [s for s in _spans_after(mark) if s.name == "speculative.scan"]
    assert sp.attrs["total_chunks"] == res.speculation.total_chunks > 0
    assert sp.attrs["hit_chunks"] == res.speculation.hit_chunks


def test_a_corpus_job_streams_under_its_shard(tmp_path):
    docs = [_doc(L, seed=L) for L in (40, 3 * BLOCK + 5, 60, 2 * BLOCK)]
    job = CorpusJob(BANK, CorpusManifest.from_docs(docs, shard_docs=4),
                    tmp_path / "job", plan=_plan(), stream_threshold=BLOCK,
                    alphabet="ACGT")
    mark = _mark()
    assert job.run().complete
    spans = _spans_after(mark)
    by_id = {s.span_id: s for s in spans}
    (stream,) = [s for s in spans if s.name == "scanner.stream"]
    assert (stream.attrs["docs"], stream.attrs["blocks"],
            stream.attrs["steps"]) == (2, 5, 3)
    assert by_id[stream.parent_id].name == "jobs.shard"
    assert np.array_equal(job.aggregate().hits,
                          job.scanner.scan(docs).hits)


def test_streamed_documents_share_one_round_trip_per_step_and_group(
        scanner):
    """Three documents of 4, 2 and 1 full blocks (ragged tails of two
    lengths) stream side by side in 4 lanes: 4 steps, each one round trip
    per group, every tail under ``scanner.tail``."""
    lengths = (4 * BLOCK + 9, 2 * BLOCK + 9, BLOCK + 30)
    docs = [_doc(L, seed=10 + L) for L in lengths]
    before = obs.snapshot()
    mark = _mark()
    res = scanner.stream_docs(docs)
    moved = obs.snapshot_delta(before, obs.snapshot())
    spans = _spans_after(mark)
    (stream,) = [s for s in spans if s.name == "scanner.stream"]
    device = [s for s in spans if s.name == "scanner.device"]
    assert len(device) == 4 * len(scanner.groups)
    assert all(s.attrs["path"] == "stream" and s.attrs["docs"] == 4
               and s.attrs["length"] == BLOCK for s in device)
    tails = [s for s in spans if s.name == "scanner.tail"]
    assert sorted((s.attrs["docs"], s.attrs["symbols"]) for s in tails) == \
        [(1, 30), (2, 9)]
    assert stream.attrs["docs"] == 3 and stream.attrs["steps"] == 4
    assert stream.attrs["blocks"] == moved["engine.stream_blocks"] == 7
    assert stream.attrs["residues"] == sum(lengths) == \
        moved["engine.stream_residues"]
    assert stream.attrs["h2d_bytes"] == sum(s.attrs["h2d_bytes"]
                                            for s in device)
    assert moved["speculative.total_chunks"] == res.speculation.total_chunks
    assert np.array_equal(res.hits, scanner.scan(docs).hits)
