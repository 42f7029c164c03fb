"""The trace reduction on a profiler trace recorded on one TPU v5 lite.

``data/chip_trace_batch.xplane.pb.gz`` is the trace of a two-shard window of
``prosite23-batch.swissprot`` (``bench/run.py --seconds 1 --trace 1``);
``data/chip_trace_batch.result.json`` is the result line that run printed.
Reducing the committed trace again must give what the run reported.
"""

import gzip
import json
from pathlib import Path

import pytest

from bench import roofline, spec, trace_reduce

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("chip_trace") / "batch.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "chip_trace_batch.xplane.pb.gz").read_bytes()))
    return trace_reduce.reduce_trace(path)


@pytest.fixture(scope="module")
def reported():
    return json.loads((DATA / "chip_trace_batch.result.json").read_text())


def test_reduction_repeats_what_the_run_reported(reduced, reported):
    assert reduced["busy_s"] == reported["device"]["busy_s"]
    assert reduced["window_s"] == reported["device"]["window_s"]
    assert reduced["breakdown"] == reported["breakdown"]


def test_busy_and_idle_fill_the_window(reduced):
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    idle = sum(s for _, s in reduced["breakdown"]["idle_gaps"])
    # At most ten gap owners are listed; a two-shard window has fewer.
    assert len(reduced["breakdown"]["idle_gaps"]) < 10
    assert idle + reduced["busy_s"] == pytest.approx(reduced["window_s"],
                                                     rel=1e-9)


def test_the_window_runs_the_scan_programs_and_no_kernel(reduced):
    # A corpus job's window runs the two scan executors, and the device is
    # busy only inside them.
    assert set(reduced["programs"]) == {"jit_bank_doc_mappings",
                                        "jit_bank_doc_mappings_sfa"}
    assert sum(reduced["programs"].values()) == pytest.approx(
        reduced["busy_s"], rel=1e-3)
    # The default plan scans with XLA executors: no Pallas kernel runs, and
    # no roofline share is reported.
    assert reduced["kernels"] == {}
    ctx = {"trace": reduced, "device_kind": "TPU v5 lite"}
    for k in trace_reduce.KERNELS:
        assert roofline.kernel_share(ctx, k) is None


@pytest.mark.parametrize("metric", ["engine.scan_device_ms_per_Mres.batch",
                                    "device.idle_share.batch"])
def test_readers_repeat_what_the_run_reported(reduced, reported, metric):
    # The run's window: two shards of 1,024 sequences, of 236 and 444 residues.
    ctx = {"trace": reduced, "window": {"residues": 696_320}}
    assert spec.reader(metric)(ctx) == reported["metrics"][metric]["value"]
