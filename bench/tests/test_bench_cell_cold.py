"""Whole runs of ``prosite23-web.cold-patterns`` on the CPU at a small size, skipping the look
for a chip: sound, the control in the program's place, and each fault the
cell can have planted under the timed path (see ``cpu_cell.py``)."""

import pytest

from bench.tests.cells import planned_checkout, run_cases
from bench.tests.cpu_cell import FAULTS

CELL = "prosite23-web.cold-patterns"


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("cold")
    return run_cases(CELL, tmp, root=planned_checkout(tmp, CELL))


def test_sound_run_is_correct(results):
    r = results["sound"]
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r["checks"]) == ["hit_mismatches", "answers_missing"]
    assert set(r["metrics"]) == {"cold_pattern_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


@pytest.mark.parametrize("case", ["control", *FAULTS])
def test_broken_run_is_not_correct(results, case):
    r = results[case]
    assert r["correct"] is False
    assert r["checks"]["hit_mismatches"]["value"] > 0
