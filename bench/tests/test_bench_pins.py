"""SHA-256 pins of what the generators draw for the protein configurations:
the warm-up documents, the first two cycles of the closed-loop generator,
the first 32 requests of the open-loop generator on each web mix, and the
control's folded text. The pins were computed before the configurations
declared their alphabet, length model and control, so these tests show that
every seed still draws the same inputs, byte for byte."""

import functools
import hashlib
import json

import pytest

from bench import reference, spec

SEEDS = (3_000_000_019, 2**33 + 5)
#: Small shards keep the test quick; the code path is the one a cell runs.
SHARD_DOCS = 64
#: Each request stream is drawn for a window of this length.
SECONDS = 60.0

PINS = {
    "prosite23-batch/3000000019": {
        "warmup":
            "9de6d862a1124a00785253b43b5363418997d09172107f80f7f922beda647c1b",
        "cycle0":
            "cf03987121343c5fa28b6ba0c089c528e7d318d8e2353a4449eede5258876533",
        "cycle1":
            "6a375cc31b7de4f06f6286963d659af98ee68c88bead03c34e35b54f65a3ae28",
        "control":
            "f986945ecbead67c1ec030ed8c920a4eb810c59dc73f7dc768c611541e8aa8a2",
        "poisson":
            "9dd729198d585c6bd3d793a72aa68fc184f14be36e90e823e584ca70501b5bb3",
        "cold-patterns":
            "cfcb1f836925eb3d40a69549bf0b362f92ccb0fff8f5a6a3c6a63c0dbab98e60",
    },
    "prosite23-batch/8589934597": {
        "warmup":
            "7dab86f9b29de02b52633d99c7be6f343897bbf3df9970a17f5f77b5d34699ce",
        "cycle0":
            "b8d7cef22cf9f36c8b29d8c8ed6cd822c05ebcfcc5899aa9afafacaa982f8519",
        "cycle1":
            "d3d14e2904043a5c7b736977951335d59f9d87399b87a95a139f4ef2577c0cf4",
        "control":
            "db5704bb8e20a30bb28299f90a220c295a838027dc6e49fa536236cb0a38c519",
        "poisson":
            "e215cfe52206072399f7bcf9813235aa378495a308bf260014850475203aa1bb",
        "cold-patterns":
            "86ca108bdce7a2d5bcd4bddb1c648e2bca941f085a0a1e89fdaa67b59f37dd53",
    },
    "prosite23-web/3000000019": {
        "warmup":
            "9de6d862a1124a00785253b43b5363418997d09172107f80f7f922beda647c1b",
        "cycle0":
            "cf03987121343c5fa28b6ba0c089c528e7d318d8e2353a4449eede5258876533",
        "cycle1":
            "6a375cc31b7de4f06f6286963d659af98ee68c88bead03c34e35b54f65a3ae28",
        "control":
            "f986945ecbead67c1ec030ed8c920a4eb810c59dc73f7dc768c611541e8aa8a2",
        "poisson":
            "9dd729198d585c6bd3d793a72aa68fc184f14be36e90e823e584ca70501b5bb3",
        "cold-patterns":
            "cfcb1f836925eb3d40a69549bf0b362f92ccb0fff8f5a6a3c6a63c0dbab98e60",
    },
    "prosite23-web/8589934597": {
        "warmup":
            "7dab86f9b29de02b52633d99c7be6f343897bbf3df9970a17f5f77b5d34699ce",
        "cycle0":
            "b8d7cef22cf9f36c8b29d8c8ed6cd822c05ebcfcc5899aa9afafacaa982f8519",
        "cycle1":
            "d3d14e2904043a5c7b736977951335d59f9d87399b87a95a139f4ef2577c0cf4",
        "control":
            "db5704bb8e20a30bb28299f90a220c295a838027dc6e49fa536236cb0a38c519",
        "poisson":
            "e215cfe52206072399f7bcf9813235aa378495a308bf260014850475203aa1bb",
        "cold-patterns":
            "86ca108bdce7a2d5bcd4bddb1c648e2bca941f085a0a1e89fdaa67b59f37dd53",
    },
}


def _sha(obj) -> str:
    return hashlib.sha256(json.dumps(obj).encode()).hexdigest()


@functools.lru_cache(maxsize=None)
def _hashes(config: str, seed: int) -> dict:
    cfg = json.loads(spec.config_path(config).read_text())
    batch = json.loads(spec.traffic_path("swissprot").read_text())
    shards = spec.generator("shards")
    cycle0 = shards.cycle_docs(cfg, batch, SHARD_DOCS, seed, 0)
    out = {
        "warmup": _sha(shards.warmup_docs(cfg, batch, SHARD_DOCS, seed)),
        "cycle0": _sha(cycle0),
        "cycle1": _sha(shards.cycle_docs(cfg, batch, SHARD_DOCS, seed, 1)),
        "control": _sha([reference.control(cfg, d) for d in cycle0]),
    }
    for mix in ("poisson", "cold-patterns"):
        traffic = json.loads(spec.traffic_path(mix).read_text())
        reqs = spec.generator("open_loop").requests(
            cfg, traffic, seed, SECONDS, set(cfg["bank"].values()))[:32]
        out[mix] = _sha([[r["due"], r["docs"], r["patterns"], r["reference"]]
                         for r in reqs])
    return out


@pytest.mark.parametrize("item", ["warmup", "cycle0", "cycle1", "control",
                                  "poisson", "cold-patterns"])
@pytest.mark.parametrize("key", list(PINS))
def test_inputs_are_byte_identical(key, item):
    config, seed = key.split("/")
    assert _hashes(config, int(seed))[item] == PINS[key][item]


#: The info line a run of either configuration prints about its clip, as it
#: read before the line moved from the harness to the drivers.
REDUCED = ("reduced: the clip to [20, 4096] removes 0.005553% of entries and "
           "0.040247% of residues; class lengths "
           "[34, 65, 125, 236, 444, 837, 1590, 2978]")


@pytest.mark.parametrize("cell", ["prosite23-batch.swissprot",
                                  "prosite23-web.poisson"])
def test_reduced_line_is_unchanged(cell):
    config, traffic = cell.split(".", 1)
    cfg = json.loads(spec.config_path(config).read_text())
    t = json.loads(spec.traffic_path(traffic).read_text())
    c = {"config": cfg, "traffic": t, "bench": str(spec.BENCH)}
    drv = spec.driver(cfg["deployment"]["kind"])(c, 1, SECONDS, None)
    assert drv.info == [REDUCED]
