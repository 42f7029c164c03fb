"""Whole runs of ``prosite23-web.poisson`` on the CPU at a small size, skipping the look
for a chip: sound, the control in the program's place, and each fault the
cell can have planted under the timed path (see ``cpu_cell.py``)."""

import pytest

from bench.tests.cells import planned_checkout, run_cases
from bench.tests.cpu_cell import FAULTS

CELL = "prosite23-web.poisson"


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("poisson")
    return run_cases(CELL, tmp, root=planned_checkout(tmp, CELL))


def test_sound_run_is_correct(results):
    r = results["sound"]
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r["checks"]) == ["hit_mismatches", "answers_missing"]
    assert set(r["metrics"]) == {"request_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


@pytest.mark.parametrize("case", ["control", *FAULTS])
def test_broken_run_is_not_correct(results, case):
    r = results[case]
    assert r["correct"] is False
    assert r["checks"]["hit_mismatches"]["value"] > 0


def test_a_new_mix_is_data_alone(tmp_path):
    """Bursty arrivals and Zipf-popular signature subsets: a traffic file
    the general generator reads, run by name with no new code."""
    import json

    from bench import spec
    from bench.tests.cells import new_checkout, planned_metrics

    cfg = json.loads(spec.config_path("prosite23-web").read_text())
    traffic = {"why": "test", "generator": "open_loop", "rate_per_s": 2.0,
               "bursts": {"every_s": 2.0, "length_s": 0.5, "factor": 4.0},
               "template_seed": 9, "sequences_per_request": [1, 3],
               "warmup_seconds": 1, "grace_seconds": 120,
               "patterns": {"kind": "subset", "sizes": [1, 2], "zipf_s": 1.5}}
    e2e = [dict(m) for m in planned_metrics(CELL)[0]]
    for m in e2e:
        m.pop("workloads", None)
    root = new_checkout(
        tmp_path, {"name": "prosite23-web.bursty-subsets",
                   "config": "prosite23-web", "traffic": "bursty-subsets",
                   "chips": 1, "why": "test"}, cfg, traffic, e2e, [])
    r = run_cases("prosite23-web.bursty-subsets", tmp_path, root=root,
                  cases="sound,control")
    assert r["sound"]["correct"] is True, r["sound"]["checks"]
    assert set(r["sound"]["metrics"]) == {"setup_s", "request_p95_ms"}
    assert r["control"]["correct"] is False
