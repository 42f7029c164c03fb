"""BENCHMARK.json and every file it names: present, parseable, within the
benchmark's naming rules, and found by name alone."""

import json
import re

import pytest

from bench import spec
from bench.tests.cells import PLANNED, planned_checkout

BM = spec.load_benchmark()
PLANNED_CELLS = {w["name"] for w in PLANNED["workloads"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert BM["command"][1] == "bench/run.py"
    assert 1 <= BM["run_seconds"] <= 51


@pytest.mark.parametrize("w", BM["workloads"] + PLANNED["workloads"],
                         ids=lambda w: w["name"])
def test_workload_files_parse(w, tmp_path):
    root = (planned_checkout(tmp_path, w["name"])
            if w["name"] in PLANNED_CELLS else spec.ROOT)
    c = spec.cell(w["name"], root)
    assert c["config"]["name"] == w["config"]
    assert c["workload"]["chips"] in (1, 4)
    for key in ("source", "assumed", "reduced", "guarantees", "checks",
                "bank", "alphabet", "sequences", "control", "deployment"):
        assert key in c["config"], key
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"], "every cell reports a per-layer metric"


@pytest.mark.parametrize(
    "m", BM["end_to_end"] + BM["per_layer"] + PLANNED["end_to_end"]
    + PLANNED["per_layer"], ids=lambda m: m["name"])
def test_metric_has_a_reader(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert callable(spec.reader(m["name"]))


def test_a_metric_split_by_cell_shares_its_reader():
    assert spec.reader("device.idle_share.any-new-cell") is not None
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_quantity.web")


def test_config_entries_match_files():
    for c in BM["configs"] + PLANNED["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert NAME.match(c["name"])


#: What a PROSITE-syntax signature may hold besides the alphabet's letters.
PROSITE_SYNTAX = set("x-[]{}()<>,0123456789")


@pytest.mark.parametrize("c", BM["configs"] + PLANNED["configs"],
                         ids=lambda c: c["name"])
def test_config_declares_its_alphabet_and_control(c):
    """The alphabet, the length model's composition, the bank and the
    control agree, whatever the alphabet is."""
    cfg = json.loads((spec.ROOT / c["file"]).read_text())
    alphabet = cfg["alphabet"]
    assert alphabet and len(set(alphabet)) == len(alphabet)
    assert sorted(cfg["sequences"]["composition"]) == sorted(alphabet)
    for key in ("median", "sigma", "clip", "class_edges"):
        assert key in cfg["sequences"], key
    for sid, pattern in cfg["bank"].items():
        assert set(pattern) <= set(alphabet) | PROSITE_SYNTAX, sid
    src, dst = cfg["control"]["fold"]
    assert len(src) == len(dst) and cfg["control"]["what"]
    assert set(src) | set(dst) <= set(alphabet)
    assert any(a != b for a, b in zip(src, dst))


def test_moves_names_an_end_to_end_metric_of_each_cell():
    e2e = {m["name"]: m for m in BM["end_to_end"] + PLANNED["end_to_end"]}
    for m in BM["per_layer"] + PLANNED["per_layer"]:
        target = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in target.get("workloads", [w])


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        spec.cell("no-such.cell")


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         "prosite23-batch.swissprot", "--seed", str(2**31 + 7),
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
