"""Whole runs of ``prosite23-batch.swissprot`` on the CPU at a small size, skipping the look
for a chip: sound, the control in the program's place, and each fault the
cell can have planted under the timed path (see ``cpu_cell.py``)."""

import pytest

from bench.tests.cells import run_cases
from bench.tests.cpu_cell import FAULTS

CELL = "prosite23-batch.swissprot"


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    return run_cases(CELL, tmp_path_factory.mktemp("batch"))


def test_sound_run_is_correct(results):
    r = results["sound"]
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r["checks"]) == ["hit_mismatches", "answers_missing"]
    assert set(r["metrics"]) == {"scan_residues_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


@pytest.mark.parametrize("case", ["control", *FAULTS])
def test_broken_run_is_not_correct(results, case):
    r = results[case]
    assert r["correct"] is False
    assert r["checks"]["hit_mismatches"]["value"] > 0


def test_harness_finds_a_cell_it_has_never_seen(tmp_path):
    """A new configuration, a new mix (release order: shards that mix
    unquantised lengths) and a new per-layer metric are files and entries
    only: the harness runs them by name."""
    import json

    from bench import spec
    from bench.tests.cells import new_checkout

    cfg = json.loads(spec.config_path("prosite23-batch").read_text())
    cfg["name"] = "dummy-cfg"
    traffic = {"why": "test", "generator": "shards", "order": "release",
               "lengths": {"mode": "raw", "range": [100, 104]},
               "template_seed": 5, "cycle_shards": 4, "check_docs": 64}
    e2e = [dict(m) for m in spec.load_benchmark()["end_to_end"]
           if m["name"] in ("setup_s", "scan_residues_per_s")]
    for m in e2e:
        m.pop("workloads", None)
    root = new_checkout(
        tmp_path, {"name": "dummy-cfg.dummy-mix", "config": "dummy-cfg",
                   "traffic": "dummy-mix", "chips": 1, "why": "test"},
        cfg, traffic, e2e,
        [{"name": "dummy.shards", "unit": "shards", "better": "higher",
          "source": "host_clock", "layer": "test",
          "moves": "scan_residues_per_s"}])
    (root / "bench/metrics/dummy.shards.py").write_text(
        "def read(ctx):\n    return len(ctx['window']['shards'])\n")
    r = run_cases("dummy-cfg.dummy-mix", tmp_path, root=root,
                  cases="sound")["sound"]
    assert r["correct"] is True
    assert set(r["metrics"]) == {"setup_s", "scan_residues_per_s"}
