"""Shared by the cell tests: run ``cpu_cell.py`` in a child process (JAX on
the CPU, its compile cache in a temporary directory) and return its
{case: result line}."""

import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def new_checkout(tmp: Path, workload: dict, config: dict, traffic: dict,
                 end_to_end: list, per_layer: list) -> Path:
    """A checkout under ``tmp`` whose BENCHMARK.json holds one cell the
    harness has never seen: its configuration and mix are new files, and
    the code is the benchmark's own (``bench/`` without tests and outputs)."""
    import shutil

    from bench import spec

    root = tmp / "checkout"
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "out",
                                                  "__pycache__"))
    (root / "bench/configs" / f"{workload['config']}.json").write_text(
        json.dumps(config))
    (root / "bench/traffic" / f"{workload['traffic']}.json").write_text(
        json.dumps(traffic))
    bm = spec.load_benchmark()
    bm.update(workloads=[workload], end_to_end=end_to_end,
              per_layer=per_layer)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


PLANNED = json.loads((HERE / "planned.json").read_text())


def files(config: str, traffic: str) -> dict:
    """A cell's configuration and mix, read from their files by name."""
    from bench import spec

    return {"config": json.loads(spec.config_path(config).read_text()),
            "traffic": json.loads(spec.traffic_path(traffic).read_text())}


def planned_metrics(name: str) -> tuple:
    """-> (end-to-end, per-layer) entries of a planned cell, ``setup_s``
    among the first."""
    from bench import spec

    setup = [m for m in spec.load_benchmark()["end_to_end"]
             if m["name"] == "setup_s"]
    return ([m for m in PLANNED["end_to_end"] if name in m["workloads"]]
            + setup,
            [m for m in PLANNED["per_layer"] if name in m["workloads"]])


def planned_checkout(tmp: Path, name: str) -> Path:
    """A checkout whose BENCHMARK.json holds the planned cell ``name``
    (``bench/tests/planned.json``) with its metrics."""
    w = next(w for w in PLANNED["workloads"] if w["name"] == name)
    f = files(w["config"], w["traffic"])
    return new_checkout(tmp, w, f["config"], f["traffic"],
                        *planned_metrics(name))


def run_cases(cell: str, tmp: Path, root: Path | None = None,
              cases: str | None = None) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"))
    cmd = [sys.executable, str(HERE / "cpu_cell.py"), "--cell", cell,
           "--out", str(tmp / "out")]
    if root is not None:
        cmd += ["--root", str(root)]
    if cases is not None:
        cmd += ["--cases", cases]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
