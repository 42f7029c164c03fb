"""The reader of ``engine.enum_ms_per_Mres.batch`` on hand-built spans: the
enumeration round trips' wall per million window residues."""

import pytest

from bench import spec

METRIC = "engine.enum_ms_per_Mres.batch"


def _span(sid, name, wall, parent=None, **attrs):
    return {"name": name, "trace_id": "t", "span_id": sid,
            "parent_id": parent, "attrs": attrs, "t_start": float(sid),
            "wall_s": wall, "t_end": sid + wall}


def _ctx():
    """One million residues. Shard 1: an enumeration (0.2 s) and an SFA
    (0.1 s) round trip; shard 2: a speculative round trip (0.3 s) and its
    enumeration fallback (0.25 s)."""
    spans = [
        _span(1, "scanner.scan", 0.5, residues=500_000),
        _span(2, "scanner.device", 0.2, 1, mode="enumeration",
              pattern_residues=1_000_000),
        _span(3, "scanner.device", 0.1, 1, mode="sfa"),
        _span(4, "scanner.scan", 0.7, residues=500_000),
        _span(5, "speculative.scan", 0.6, 4),
        _span(6, "scanner.device", 0.3, 5, mode="speculative"),
        _span(7, "scanner.device", 0.25, 5, mode="enumeration",
              pattern_residues=300_000),
    ]
    return {"spans": spans, "trace": None,
            "window": {"residues": 1_000_000, "shards": [{}, {}]}}


def test_reads_the_enumeration_round_trips_per_mres():
    # 0.2 + 0.25 s over 1 Mres
    assert spec.reader(METRIC)(_ctx()) == pytest.approx(450.0)


@pytest.mark.parametrize("missing", ["spans", "modes", "residues"])
def test_reads_none_without_enumeration_spans_or_residues(missing):
    ctx = _ctx()
    if missing == "spans":
        ctx["spans"] = [s for s in ctx["spans"]
                        if s["attrs"].get("mode") != "enumeration"]
    elif missing == "modes":
        for s in ctx["spans"]:
            s["attrs"].pop("mode", None)
    else:
        ctx["window"] = {}
    assert spec.reader(METRIC)(ctx) is None
