"""``rebase64-batch.plasmids``: its bank is the translation of its REBASE
sites, and whole runs of the cell on the CPU at a small size, skipping the
look for a chip, read correct when sound and not correct under the control
or any fault planted under the timed path (``cpu_cell.broken``).

The runs use a sub-bank with a row in each tier (EcoRI: SFA; BglI:
enumeration; BslI: speculative) and a strand pair (BsaI, BsaI.rc), and
keep one length class above the 8,192-symbol streaming threshold, so the
stream path runs inside the window. Run as a script, this file is the
child process that makes those runs:

    JAX_PLATFORMS=cpu python bench/tests/test_bench_cell_plasmids.py \\
        --out <dir> [--cases sound,control,...]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve()
sys.path[:0] = [str(HERE.parents[2]), str(HERE.parents[2] / "src")]

import pytest  # noqa: E402

from bench import spec  # noqa: E402
from bench.tests.cpu_cell import FAULTS, broken  # noqa: E402

CELL = "rebase64-batch.plasmids"
SUB_BANK = ("EcoRI", "BglI", "BslI", "BsaI", "BsaI.rc")

#: IUPAC nucleotide codes and their complements.
IUPAC = {"A": "A", "C": "C", "G": "G", "T": "T", "R": "AG", "Y": "CT",
         "S": "CG", "W": "AT", "K": "GT", "M": "AC", "B": "CGT", "D": "AGT",
         "H": "ACT", "V": "ACG", "N": "ACGT"}
COMPLEMENT = dict(zip("ACGTRYSWKMBDHVN", "TGCAYRSWMKVHDBN"))


def reverse_complement(site: str) -> str:
    return "".join(COMPLEMENT[c] for c in reversed(site))


def to_prosite(site: str) -> str:
    """An IUPAC site in PROSITE syntax: a run of ``N`` is ``x`` or
    ``x(n)``, another degenerate code a class of its bases."""
    elems = []
    for c in site:
        if c == "N" and elems and elems[-1][0] == "x":
            elems[-1] = ("x", elems[-1][1] + 1)
        elif c == "N":
            elems.append(("x", 1))
        else:
            bases = IUPAC[c]
            elems.append((bases if len(bases) == 1 else f"[{bases}]", 1))
    return "-".join(e if n == 1 else f"{e}({n})" for e, n in elems)


def _config() -> dict:
    return json.loads(spec.config_path("rebase64-batch").read_text())


def test_bank_is_the_sites_on_both_strands():
    cfg = _config()
    sites = cfg["sites"]
    assert len(sites) == 64 and set(cfg["alphabet"]) == set("ACGT")
    want = {}
    for enzyme, site in sites.items():
        want[enzyme] = to_prosite(site)
        if reverse_complement(site) != site:
            want[f"{enzyme}.rc"] = to_prosite(reverse_complement(site))
    assert cfg["bank"] == want
    assert list(cfg["bank"]) == list(want)
    assert len(want) == 72
    assert sorted(e for e in sites
                  if reverse_complement(sites[e]) != sites[e]) == sorted(
        ["BsaI", "BsmBI", "BbsI", "SapI", "BsmI", "FokI", "MmeI", "BspMI"])


def test_the_cell_reads_its_own_driver_and_metrics():
    c = spec.cell(CELL)
    assert c["config"]["deployment"]["kind"] == "corpus_job_alphabet"
    assert {m["name"] for m in c["end_to_end"]} == {"scan_residues_per_s",
                                                    "setup_s"}
    assert len(c["per_layer"]) == 5
    from bench.drivers import corpus_job

    driver = spec.driver("corpus_job_alphabet")
    assert issubclass(driver, corpus_job.Driver)
    assert "window" not in vars(driver) and "check" not in vars(driver)


def small(c: dict) -> None:
    """The cell at a size a test run holds: the sub-bank, 4-plasmid shards
    of 3 classes (3,019, 5,976 and 9,835 bp; the last streams), every
    document of the window checked."""
    cfg = c["config"]
    cfg["bank"] = {k: cfg["bank"][k] for k in SUB_BANK}
    cfg["sequences"].update(clip=[1000, 12000],
                            class_edges=[1000, 4096, 8192, 12000])
    cfg["deployment"]["shard_docs"] = 4
    c["traffic"].update(cycle_shards=4, check_docs=64)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--cases", default="sound,control," + ",".join(FAULTS))
    ap.add_argument("--seed", type=int, default=3_000_000_019)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    from bench.harness import execute
    from repro import obs

    results = {}
    for case in args.cases.split(","):
        before = obs.snapshot("engine")
        with broken(case if case in FAULTS else None):
            out = execute(CELL, args.seed, args.seconds, False,
                          control=case == "control", require_chip=False,
                          out_root=Path(args.out), overrides=small)
        moved = obs.snapshot_delta(before, obs.snapshot("engine"))
        results[case] = out["result"] | {
            "stream_blocks": moved.get("engine.stream_blocks", 0)}
    print(json.dumps(results))


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("plasmids")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"))
    proc = subprocess.run([sys.executable, str(HERE), "--out",
                           str(tmp / "out")], env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_streams(results):
    r = results["sound"]
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"scan_residues_per_s", "setup_s"}
    # The cycle's third shard is the 9,835-bp class: 4 plasmids of 4
    # blocks each; the warm-up streams one such shard, the window more.
    assert r["attempted"] >= 3
    assert r["stream_blocks"] > 4 * 4


@pytest.mark.parametrize("case", ["control", *FAULTS])
def test_broken_run_is_not_correct(results, case):
    r = results[case]
    assert r["correct"] is False
    assert r["checks"]["hit_mismatches"]["value"] > 0


if __name__ == "__main__":
    main()
