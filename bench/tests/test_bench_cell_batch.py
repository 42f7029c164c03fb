"""Whole runs of ``prosite23-batch.swissprot`` on the CPU at a small size, skipping the look
for a chip: sound, the control in the program's place, and each fault the
cell can have planted under the timed path (see ``cpu_cell.py``)."""

import json

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


def _batch_end_to_end() -> list:
    """The batch cell's end-to-end metrics, for a cell of any name."""
    from bench import spec

    e2e = [dict(m) for m in spec.load_benchmark()["end_to_end"]
           if m["name"] in ("setup_s", "scan_residues_per_s")]
    for m in e2e:
        m.pop("workloads", None)
    return e2e


def test_harness_finds_a_cell_it_has_never_seen(tmp_path):
    """A new configuration, a new mix (release order: shards that mix
    unquantised lengths) and a new per-layer metric are files and entries
    only: the harness runs them by name."""
    from bench import spec
    from bench.tests.cells import new_checkout

    cfg = json.loads(spec.config_path("prosite23-batch").read_text())
    cfg["name"] = "dummy-cfg"
    traffic = {"why": "test", "generator": "shards", "order": "release",
               "lengths": {"mode": "raw", "range": [100, 104]},
               "template_seed": 5, "cycle_shards": 4, "check_docs": 64}
    root = new_checkout(
        tmp_path, {"name": "dummy-cfg.dummy-mix", "config": "dummy-cfg",
                   "traffic": "dummy-mix", "chips": 1, "why": "test"},
        cfg, traffic, _batch_end_to_end(),
        [{"name": "dummy.shards", "unit": "shards", "better": "higher",
          "source": "host_clock", "layer": "test",
          "moves": "scan_residues_per_s"}])
    (root / "bench/metrics/dummy.shards.py").write_text(
        "def read(ctx):\n    return len(ctx['window']['shards'])\n")
    r = run_cases("dummy-cfg.dummy-mix", tmp_path, root=root,
                  cases="sound")["sound"]
    assert r["correct"] is True
    assert set(r["metrics"]) == {"setup_s", "scan_residues_per_s"}


#: A restriction-map deployment over DNA, as new files only: REBASE
#: recognition sites in PROSITE syntax, whose automata (7, 37 and 189
#: states) go to the scan's SFA, enumeration and speculative tiers; 50.8% GC
#: as in E. coli K-12 MG1655; a 1-bit control (purine or pyrimidine).
DNA_CONFIG = {
    "name": "rebase-dna",
    "source": "http://rebase.neb.com/ (Type II recognition sites)",
    "deployment": {"kind": "corpus_job", "shard_docs": 1024},
    "alphabet": "ACGT",
    "bank": {"EcoRI": "G-A-A-T-T-C", "BglI": "G-C-C-x(5)-G-G-C",
             "BslI": "C-C-x(7)-G-G"},
    "sequences": {"median": 300, "sigma": 0.5, "clip": [20, 4096],
                  "class_edges": [20, 40, 80, 160, 320, 640, 1280, 2560,
                                  4096],
                  "composition": {"A": 24.6, "C": 25.4, "G": 25.4,
                                  "T": 24.6}},
    "checks": {"hit_mismatches": 0, "answers_missing": 0},
    "control": {"fold": ["GT", "AC"],
                "what": "bases stored in 1 bit: purine or pyrimidine"},
}
DNA_CELL = "rebase-dna.dna-shards"


@pytest.fixture(scope="module")
def dna_results(tmp_path_factory):
    from bench import spec
    from bench.tests.cells import new_checkout

    tmp = tmp_path_factory.mktemp("dna")
    traffic = json.loads(spec.traffic_path("swissprot").read_text())
    root = new_checkout(
        tmp, {"name": DNA_CELL, "config": "rebase-dna",
              "traffic": "dna-shards", "chips": 1, "why": "test"},
        DNA_CONFIG, traffic, _batch_end_to_end(), [])
    return run_cases(DNA_CELL, tmp, root=root)


@pytest.mark.parametrize("case", ["sound", "control", *FAULTS])
def test_a_configuration_over_another_alphabet_is_files_alone(dna_results,
                                                               case):
    """A 4-letter alphabet, its composition, its bank and its control fold
    come from the configuration alone: the unchanged harness reads a sound
    run as correct, and the control and each fault as not correct."""
    r = dna_results[case]
    if case == "sound":
        assert r["correct"] is True, r["checks"]
        assert r["failed"] == 0 and r["attempted"] > 0
        assert set(r["metrics"]) == {"setup_s", "scan_residues_per_s"}
    else:
        assert r["correct"] is False
        assert r["checks"]["hit_mismatches"]["value"] > 0
