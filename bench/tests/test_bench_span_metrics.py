"""The readers of the program's spans and transfer counters, on hand-built
contexts, and the unattributed idle share on the committed chip trace."""

import gzip
import json
from pathlib import Path

import pytest

from bench import spec, trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def _span(sid, name, wall, parent=None, **attrs):
    return {"name": name, "trace_id": "t", "span_id": sid,
            "parent_id": parent, "attrs": attrs, "t_start": float(sid),
            "wall_s": wall, "t_end": sid + wall}


def _ctx():
    """Two shards of 500,000 residues. Shard 1: a scan of 0.5 s holding two
    device calls (0.2 s, 0.1 s); shard 2: a scan of 0.4 s whose device call
    (0.25 s) sits under a speculative span. Between them the job's own
    spans; a device call outside any scan does not count."""
    spans = [
        _span(1, "jobs.pending", 0.004),
        _span(2, "jobs.shard", 0.52),
        _span(3, "scanner.scan", 0.5, 2, h2d_bytes=16_000_000,
              d2h_bytes=2_000_000, residues=500_000),
        _span(4, "scanner.device", 0.2, 3),
        _span(5, "scanner.device", 0.1, 3),
        _span(6, "jobs.checkpoint", 0.006, 2),
        _span(7, "flight.record", 0.01),
        _span(8, "jobs.shard", 0.42),
        _span(9, "scanner.scan", 0.4, 8, h2d_bytes=15_000_000,
              d2h_bytes=2_500_000, residues=500_000),
        _span(10, "speculative.scan", 0.3, 9),
        _span(11, "scanner.device", 0.25, 10),
        _span(12, "jobs.checkpoint", 0.004, 8),
        _span(13, "flight.record", 0.02),
        _span(14, "scanner.device", 5.0),
    ]
    return {"spans": spans, "trace": None,
            "window": {"residues": 1_000_000, "shards": [{}, {}]}}


WANT = {
    # (0.5 + 0.4) - (0.2 + 0.1 + 0.25) = 0.35 s over 1 Mres
    "engine.host_ms_per_Mres.batch": 350.0,
    "engine.h2d_bytes_per_res.batch": 31.0,
    "engine.d2h_bytes_per_res.batch": 4.5,
    # 0.006 + 0.01 + 0.004 + 0.02 = 0.040 s over 2 shards; the probe
    # (jobs.pending) is left out
    "jobs.host_ms_per_shard.batch": 20.0,
}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_gives_the_hand_computed_value(metric):
    assert spec.reader(metric)(_ctx()) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(WANT))
@pytest.mark.parametrize("missing", ["spans", "residues"])
def test_reader_gives_none_without_spans_or_residues(metric, missing):
    ctx = _ctx()
    if missing == "spans":
        ctx["spans"] = [s for s in ctx["spans"]
                        if s["name"] in ("jobs.shard", "scanner.scan")]
        for s in ctx["spans"]:
            s["attrs"] = {}
    else:
        ctx["window"] = {}
    assert spec.reader(metric)(ctx) is None


def test_unattributed_idle_share_on_a_hand_built_trace():
    read = spec.reader("device.unattributed_idle_share.batch")
    tr = {"window_s": 2.0, "busy_s": 1.5,
          "breakdown": {"idle_gaps": [["scanner.scan", 0.4],
                                      ["no span", 0.1]]}}
    assert read({"trace": tr}) == pytest.approx(5.0)
    tr["breakdown"]["idle_gaps"] = [["scanner.scan", 0.5]]
    assert read({"trace": tr}) == 0.0
    assert read({"trace": None}) is None


def test_unattributed_idle_share_on_the_chip_trace(tmp_path):
    path = tmp_path / "batch.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "chip_trace_batch.xplane.pb.gz").read_bytes()))
    reduced = trace_reduce.reduce_trace(path)
    reported = json.loads((DATA / "chip_trace_batch.result.json").read_text())
    no_span = dict(reported["breakdown"]["idle_gaps"])["no span"]
    got = spec.reader("device.unattributed_idle_share.batch")(
        {"trace": reduced})
    assert got == pytest.approx(
        100 * no_span / reported["device"]["window_s"], rel=1e-12)
    assert 0 < got < 100
