"""The Scanner engine: one entry point, every configuration, one answer.

Differential tests pin the engine's core contract: every (mode, backend,
distribution, chunking) plan produces bit-identical results, ``auto`` mode
picks SFA exactly when construction fits the budget, ``stream()`` equals
``scan()`` on the concatenated input, and the executors module is the one
home of the parallel entry points (the PR-2 deprecation shims are gone).
"""

import jax.numpy as jnp
import numpy as np
import pytest
from _strategies import given, settings, st

from repro.compat import make_mesh
from repro.core.dfa import random_dfa
from repro.core.prosite import PROSITE_SAMPLES, compile_prosite, load_bank, synthetic_protein
from repro.core.sfa import StateBlowup, construct_sfa
from repro.engine import ChunkPolicy, ScanPlan, Scanner


def _random_docs(seed, n_docs, length, k):
    rng = np.random.default_rng(seed)
    return rng.integers(0, k, size=(n_docs, length)).astype(np.int32)


# --------------------------------------------------------------------------
# Plans / compilation
# --------------------------------------------------------------------------


def test_plan_validation():
    with pytest.raises(ValueError):
        ScanPlan(mode="magic").validate()
    with pytest.raises(ValueError):
        ScanPlan(backend="cuda").validate()
    with pytest.raises(ValueError):
        ScanPlan(distribution="shard_map", backend="pallas").validate()
    with pytest.raises(ValueError):
        ScanPlan(chunking=ChunkPolicy(n_chunks=0)).validate()
    assert ScanPlan().with_(mode="sfa").mode == "sfa"


def test_compile_accepts_all_pattern_forms():
    dfa = compile_prosite("R-G-D")
    bank = load_bank(["PS00016", "PS00001"])
    for pats, n in [
        ("PS00016", 1),                      # bundled PROSITE id
        ("R-G-D", 1),                        # PROSITE signature syntax
        (dfa, 1),                            # compiled DFA
        (bank, 2),                           # PatternBank
        (["PS00016", dfa], 2),               # mixed sequence
        ({"a": "R-G-D", "b": "C-x(2)-C"}, 2),  # mapping
    ]:
        sc = Scanner.compile(pats)
        assert sc.n_patterns == n
    assert Scanner.compile("PS00016").single
    assert not Scanner.compile(["PS00016"]).single


def test_auto_mode_respects_state_budget():
    """The acceptance criterion: SFA iff construction fits the budget."""
    sc = Scanner.compile(["PS00016", "PS00008"],
                         ScanPlan(mode="auto", sfa_state_budget=20))
    assert sc.pattern_modes["PS00016"] == "sfa"       # tiny SFA
    assert sc.pattern_modes["PS00008"] == "enumeration"  # blows the budget
    # the budget really is the boundary: PS00016's SFA fits in 20 states
    assert construct_sfa(compile_prosite(
        PROSITE_SAMPLES["PS00016"])).n_states <= 20
    with pytest.raises(StateBlowup):
        Scanner.compile("PS00008", ScanPlan(mode="sfa", sfa_state_budget=20))


# --------------------------------------------------------------------------
# auto == sfa == enumeration (property, random DFAs)
# --------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    cfg=st.tuples(st.integers(min_value=0, max_value=400),
                  st.sampled_from([1, 2, 4])),
    forced=st.one_of(st.sampled_from(["sfa"]), st.sampled_from(["enumeration"])),
)
def test_auto_agrees_with_forced_modes(cfg, forced):
    seed, n_chunks = cfg
    k = 5
    dfas = [random_dfa(3 + (seed + i) % 3, k, seed=seed * 7 + i) for i in range(3)]
    docs = _random_docs(seed, 4, 33, k)  # 33: exercises the ragged tail
    plan = ScanPlan(mode="auto", sfa_state_budget=10_000,
                    chunking=ChunkPolicy(n_chunks=n_chunks))
    auto = Scanner.compile(dfas, plan).scan(docs).hits
    other = Scanner.compile(dfas, plan.with_(mode=forced)).scan(docs).hits
    assert np.array_equal(auto, other)
    # and both agree with the plain sequential DFA
    for p, d in enumerate(dfas):
        for j in range(docs.shape[0]):
            assert auto[p, j] == bool(d.accepting[d.run(docs[j])]), (p, j)


# --------------------------------------------------------------------------
# Backends bit-identical on the bundled PROSITE bank
# --------------------------------------------------------------------------


def test_backends_bit_identical_on_bundled_bank():
    bank = load_bank()
    docs = [synthetic_protein(48, seed=i) for i in range(3)]
    plan = ScanPlan(mode="auto", chunking=ChunkPolicy(n_chunks=4))
    results = {}
    mappings = {}
    for backend in ("reference", "xla", "pallas"):
        sc = Scanner.compile(bank, plan.with_(backend=backend))
        results[backend] = sc.scan(docs).hits
        mappings[backend] = sc.mapping(docs[0])
    # mixed modes were actually exercised under the default budget
    sc = Scanner.compile(bank, plan)
    assert {"sfa", "enumeration"} <= set(sc.pattern_modes.values())
    assert np.array_equal(results["reference"], results["xla"])
    assert np.array_equal(results["xla"], results["pallas"])
    assert np.array_equal(mappings["reference"], mappings["xla"])
    assert np.array_equal(mappings["xla"], mappings["pallas"])


def test_shard_map_distribution_matches_local():
    k = 6
    dfas = [random_dfa(4 + i, k, seed=50 + i) for i in range(3)]
    docs = _random_docs(5, 4, 32, k)
    plan = ScanPlan(mode="auto", sfa_state_budget=10_000,
                    chunking=ChunkPolicy(n_chunks=4))
    local = Scanner.compile(dfas, plan).scan(docs).hits
    dist = Scanner.compile(
        dfas, plan.with_(distribution="shard_map",
                         mesh=make_mesh((1,), ("data",)))
    ).scan(docs).hits
    assert np.array_equal(local, dist)


_DEFAULT_MESH_SCRIPT = """
import sys
import jax
import numpy as np
from repro.core.prosite import load_bank, synthetic_protein
from repro.engine import ChunkPolicy, ConstructionPolicy, ScanPlan, Scanner

bank = load_bank(["PS00001", "PS00005", "PS00016", "PS00017"])
docs = [synthetic_protein(64, seed=i) for i in range(8)]
plan = ScanPlan(chunking=ChunkPolicy(n_chunks=4),
                construction=ConstructionPolicy(cache="off"))
local = Scanner.compile(bank, plan)
dist = Scanner.compile(bank, plan.with_(distribution="shard_map"))
assert jax.device_count() == 4, jax.devices()
assert dist.mesh.size == 4, dist.mesh
assert np.array_equal(local.scan(docs).hits, dist.scan(docs).hits)
print("OK")
"""


def test_shard_map_default_mesh_spans_all_devices():
    """With no mesh given, the scan's shard_map mesh spans every device (4
    virtual CPU devices in a child process) and matches local bit for bit."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = Path(__file__).resolve().parents[1] / "src"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(src)}
    r = subprocess.run([sys.executable, "-c", _DEFAULT_MESH_SCRIPT],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().splitlines()[-1] == "OK"


def test_bucketed_plan_matches_unbucketed():
    k = 6
    dfas = [random_dfa(n, k, seed=60 + n) for n in (2, 3, 9, 17, 5)]
    docs = _random_docs(6, 3, 40, k)
    plan = ScanPlan(mode="enumeration", chunking=ChunkPolicy(n_chunks=4))
    plain = Scanner.compile(dfas, plan).scan(docs).hits
    bucketed = Scanner.compile(dfas, plan.with_(
        chunking=ChunkPolicy(n_chunks=4, bucket=True, bucket_edges=(4, 8, 16))
    )).scan(docs).hits
    assert np.array_equal(plain, bucketed)


# --------------------------------------------------------------------------
# stream() == scan() (property: arbitrary piece splits, every backend)
# --------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=200),
    sizes=st.lists(st.integers(min_value=1, max_value=57),
                   min_size=1, max_size=8),
    backend=st.sampled_from(["xla", "pallas", "reference"]),
)
def test_stream_equals_scan_on_concatenation(seed, sizes, backend):
    k = 5
    dfas = [random_dfa(3 + i, k, seed=seed * 3 + i) for i in range(2)]
    plan = ScanPlan(mode="auto", sfa_state_budget=10_000, backend=backend,
                    chunking=ChunkPolicy(n_chunks=2, block_len=8))
    sc = Scanner.compile(dfas, plan)
    # corpus >= 8x the (n_chunks * block_len) super-block, plus a ragged tail
    rng = np.random.default_rng(seed)
    total = 8 * (2 * 8) + int(rng.integers(0, 23))  # 8 super-blocks + tail
    corpus = rng.integers(0, k, size=total).astype(np.int32)
    # split the corpus into the drawn piece sizes (cycled to cover it all)
    pieces, lo, i = [], 0, 0
    while lo < total:
        hi = min(total, lo + sizes[i % len(sizes)])
        pieces.append(corpus[lo:hi])
        lo, i = hi, i + 1
    res = sc.stream(pieces)
    assert res.n_symbols == total
    assert np.array_equal(res.mapping, sc.mapping(corpus))
    assert np.array_equal(res.accepted, sc.scan([corpus]).hits[:, 0])


def test_stream_session_push_api_and_reuse_errors():
    sc = Scanner.compile("R-G-D", ScanPlan(
        chunking=ChunkPolicy(n_chunks=2, block_len=8)))
    text = synthetic_protein(200, seed=0) + "RGD"
    sess = sc.open_stream()
    for i in range(0, len(text), 31):
        sess.feed(text[i: i + 31])
    res = sess.finish()
    assert res.accepts is True
    assert res.single
    with pytest.raises(RuntimeError):
        sess.feed("AAA")
    with pytest.raises(RuntimeError):
        sess.finish()


def test_stream_matches_scan_on_long_corpus():
    """Acceptance: corpus >= 8x the chunk-block size, block-parallel path hot."""
    plan = ScanPlan(mode="auto", chunking=ChunkPolicy(n_chunks=4, block_len=16))
    sc = Scanner.compile(["PS00016", "PS00001"], plan)
    text = synthetic_protein(4 * 16 * 11 + 7, seed=3)   # 11 full super-blocks
    res = sc.stream(text[i: i + 100] for i in range(0, len(text), 100))
    assert np.array_equal(res.accepted, sc.scan([text]).hits[:, 0])
    assert np.array_equal(res.mapping, sc.mapping(text))


# --------------------------------------------------------------------------
# Legacy entry points: removed after the PR-2 deprecation window; executors
# is the single home and it agrees with the Scanner
# --------------------------------------------------------------------------

REMOVED_LEGACY_NAMES = [
    ("repro.core.matching", "match_parallel_enumeration"),
    ("repro.core.matching", "match_parallel_sfa"),
    ("repro.core.matching", "find_matches_parallel"),
    ("repro.core.matching", "accepts_parallel"),
    ("repro.core.matching", "distributed_match_fn"),
    ("repro.core.matching", "throughput_matcher"),
    ("repro.core.multipattern", "match_bank_parallel"),
    ("repro.core.multipattern", "bank_hits"),
    ("repro.core.multipattern", "census_bank"),
    ("repro.core.multipattern", "distributed_bank_matcher"),
    ("repro.core.multipattern", "distributed_census_fn"),
]


def test_legacy_shims_are_gone():
    """The PR-2 deprecation policy ran its course: two further PRs touched
    every call site, so the shims (and the warn-once machinery) are removed.
    The engine executors remain the single home of these entry points."""
    import importlib

    import repro.core
    from repro.engine import executors as X

    for module, name in REMOVED_LEGACY_NAMES:
        assert not hasattr(importlib.import_module(module), name), \
            f"{module}.{name} should be removed"
        assert not hasattr(repro.core, name), f"repro.core.{name}"
        assert callable(getattr(X, name)), f"executors.{name} must remain"
    with pytest.raises(ImportError):
        importlib.import_module("repro.engine.deprecation")


def test_executors_match_scanner():
    """The executors' free functions agree with the Scanner facade (the
    identity half of the old shim test, now shim-free)."""
    from repro.core.multipattern import PatternBank
    from repro.engine import executors as X

    k = 6
    dfas = [random_dfa(n, k, seed=70 + n) for n in (3, 5, 4)]
    bank = PatternBank.from_dfas(dfas)
    tables, accepting, starts = bank.device_arrays()
    rng = np.random.default_rng(7)
    syms = rng.integers(0, k, size=64).astype(np.int32)
    corpus = rng.integers(0, k, size=(4, 32)).astype(np.int32)
    d0 = dfas[0]
    sfa0 = construct_sfa(d0)
    mesh = make_mesh((1, 1), ("data", "model"))

    maps_enum = np.asarray(X.match_parallel_enumeration(
        jnp.asarray(d0.table), jnp.asarray(syms), 4))
    maps_sfa = np.asarray(X.match_parallel_sfa(
        jnp.asarray(sfa0.delta), jnp.asarray(sfa0.mappings),
        jnp.asarray(syms), 4))
    assert np.array_equal(maps_enum, maps_sfa)
    assert int(maps_sfa[d0.start]) == d0.run(syms)

    bank_maps = np.asarray(X.match_bank_parallel(tables, jnp.asarray(syms), 4))
    dist_maps = np.asarray(X.distributed_bank_matcher(mesh)(
        tables, jnp.asarray(syms), 4))
    assert np.array_equal(bank_maps, dist_maps)

    sc = Scanner.compile(dfas, ScanPlan(mode="enumeration",
                                        chunking=ChunkPolicy(n_chunks=4)))
    hits = np.asarray(X.bank_hits(tables, accepting, starts,
                                  jnp.asarray(corpus), 4))
    counts = np.asarray(X.census_bank(tables, accepting, starts,
                                      jnp.asarray(corpus), 4))
    dist_counts = np.asarray(X.distributed_census_fn(mesh, n_chunks=4)(
        tables, accepting, starts, jnp.asarray(corpus)))
    assert np.array_equal(hits, sc.scan(corpus).hits)
    assert np.array_equal(counts, sc.census(corpus))
    assert np.array_equal(dist_counts, sc.census(corpus))


# --------------------------------------------------------------------------
# kernels/ops block kwarg (satellite): blocked == unblocked
# --------------------------------------------------------------------------


@pytest.mark.parametrize("block_b", [1, 3, 8, 64])
def test_match_chunks_block_b_invariant(block_b):
    from repro.kernels import ops

    d = random_dfa(6, 5, seed=9)
    chunks = jnp.asarray(
        np.random.default_rng(9).integers(0, 5, size=(5, 12)), dtype=jnp.int32)
    want = ops.match_chunks(jnp.asarray(d.table), chunks, block_b=1,
                            interpret=True)
    got = ops.match_chunks(jnp.asarray(d.table), chunks, block_b=block_b,
                           interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block_b", [1, 2, 8])
def test_match_bank_chunks_block_b_invariant(block_b):
    from repro.core.multipattern import PatternBank
    from repro.kernels import ops

    bank = PatternBank.from_dfas(
        [random_dfa(n, 4, seed=80 + n) for n in (3, 7)])
    chunks = jnp.asarray(
        np.random.default_rng(8).integers(0, 4, size=(3, 10)), dtype=jnp.int32)
    tables = jnp.asarray(bank.tables)
    want = ops.match_bank_chunks(tables, chunks, block_b=1, interpret=True)
    got = ops.match_bank_chunks(tables, chunks, block_b=block_b,
                                interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))
