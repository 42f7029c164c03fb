"""Find a cell's files by name: ``BENCHMARK.json`` -> configuration, traffic
mix, the driver its deployment kind names, the generator its mix names, and
the metric readers. Adding a cell, a mix, a deployment kind or a metric adds
files and entries; nothing in the harness names one.

* ``configs/<config>.json``, ``traffic/<mix>.json``: data;
* ``drivers/<kind>.py`` (``Driver``), ``generators/<name>.py``: code, found
  by the configuration's ``deployment.kind`` and the mix's ``generator``;
* ``metrics/<metric>.py`` (``read(ctx)``): the reader of a metric, or of
  the quantity it splits by cell: ``device.idle_share.web`` is read by
  ``metrics/device.idle_share.py`` unless a file of its own name exists.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def config_path(name: str, bench: Path = BENCH) -> Path:
    return bench / "configs" / f"{name}.json"


def traffic_path(name: str, bench: Path = BENCH) -> Path:
    return bench / "traffic" / f"{name}.json"


def metric_path(name: str, bench: Path = BENCH) -> Path:
    return bench / "metrics" / f"{name}.py"


def cell(name: str, root: Path = ROOT) -> dict:
    """-> {"workload", "config", "traffic", "end_to_end", "per_layer"} for
    the workload ``name``: the metrics are those that list it (or list no
    workloads at all)."""
    bm = load_benchmark(root)
    bench = root / "bench"
    by_name = {w["name"]: w for w in bm["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    cfg = json.loads(config_path(w["config"], bench).read_text())
    traffic = json.loads(traffic_path(w["traffic"], bench).read_text())

    def mine(m):
        return name in m.get("workloads", [name])

    return {
        "bench": str(bench),
        "workload": w,
        "config": cfg,
        "traffic": traffic,
        "end_to_end": [m for m in bm["end_to_end"] if mine(m)],
        "per_layer": [m for m in bm["per_layer"] if mine(m)],
    }


def _load(path: Path, prefix: str):
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    s = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def reader(metric: str, bench: Path = BENCH):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``, or else of the
    longest dotted prefix of ``metric`` that has a reader."""
    parts = metric.split(".")
    for n in range(len(parts), 0, -1):
        path = metric_path(".".join(parts[:n]), bench)
        if path.exists():
            return _load(path, "bench_metric_").read
    raise FileNotFoundError(f"no reader for metric {metric!r} under "
                            f"{bench / 'metrics'}")


def driver(kind: str, bench: Path = BENCH):
    """The ``Driver`` class of ``drivers/<kind>.py``."""
    return _load(bench / "drivers" / f"{kind}.py", "bench_driver_").Driver


def generator(name: str, bench: Path = BENCH):
    """The module ``generators/<name>.py``."""
    return _load(bench / "generators" / f"{name}.py", "bench_generator_")
