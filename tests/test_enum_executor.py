"""The XLA enumeration bank executor, ``bank_doc_mappings``: bit for bit
against the NumPy oracle over the shapes the engine plans (state counts up
to the speculation threshold, padded pattern stacks, both alphabets), and
its per-symbol loop free of any gather."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hlo import loop_body
from repro.core.dfa import random_dfa
from repro.engine import ChunkPolicy, ScanPlan, Scanner
from repro.engine import executors as X
from repro.engine.scanner import _reference_doc_mappings


def _padded_stack(Pg, n, k, seed):
    """(Pg, n, k) tables as a pattern group stacks them: pattern p has its
    own state count (the first has all n), and rows past it self-loop."""
    rng = np.random.default_rng(seed)
    tables = np.empty((Pg, n, k), dtype=np.int32)
    for p in range(Pg):
        n_p = n if p == 0 else int(rng.integers(1, n + 1))
        tables[p] = np.arange(n, dtype=np.int32)[:, None]
        tables[p, :n_p] = rng.integers(0, n_p, size=(n_p, k))
    return tables


@pytest.mark.parametrize("k", [4, 20])
@pytest.mark.parametrize("n", [1, 8, 32, 57, 87, 128])
@pytest.mark.parametrize("Pg", [1, 2, 3])
def test_bank_doc_mappings_matches_reference(Pg, n, k):
    tables = _padded_stack(Pg, n, k, seed=1000 * Pg + 10 * n + k)
    rng = np.random.default_rng(n + k)
    corpus = rng.integers(0, k, size=(5, 48)).astype(np.int32)
    got = np.asarray(X.bank_doc_mappings(jnp.asarray(tables),
                                         jnp.asarray(corpus), 8))
    assert got.dtype == np.int32
    assert np.array_equal(got, _reference_doc_mappings(tables, corpus))


def test_symbol_loop_holds_no_gather():
    """The loop over a chunk's 56 symbols advances every lane by compares,
    selects and one contraction; the only gathers left compose the chunk
    functions afterwards (the 7-step monoid reduce)."""
    compiled = X.bank_doc_mappings.lower(
        jax.ShapeDtypeStruct((2, 57, 20), jnp.int32),
        jax.ShapeDtypeStruct((16, 448), jnp.int32), 8).compile()
    text = compiled.as_text()
    body = loop_body(text, '"known_trip_count":{"n":"56"}')
    assert body and not [ln for ln in body if " gather(" in ln]
    # The parser does see gathers: the chunk composition keeps its own.
    assert " gather(" in text


def test_scan_enumeration_ragged_lengths_matches_reference():
    """Documents of lengths that are not multiples of n_chunks, scanned in
    enumeration mode (head on the device, tail on the host), against the
    reference backend."""
    k = 20
    dfas = [random_dfa(n, k, seed=70 + n) for n in (3, 29, 57, 12, 87)]
    rng = np.random.default_rng(5)
    docs = [rng.integers(0, k, size=int(L)).astype(np.int32)
            for L in (1, 7, 8, 9, 63, 65, 130, 131, 444, 445)]
    plan = ScanPlan(mode="enumeration", chunking=ChunkPolicy(n_chunks=8))
    got = Scanner.compile(dfas, plan).scan(docs).hits
    want = Scanner.compile(dfas, plan.with_(backend="reference")).scan(docs).hits
    assert np.array_equal(got, want)


_SHARD_MAP_SCRIPT = """
import jax
import numpy as np
from repro.core.dfa import random_dfa
from repro.engine import ChunkPolicy, ScanPlan, Scanner

k = 20
dfas = [random_dfa(n, k, seed=80 + n) for n in (5, 57, 33)]
rng = np.random.default_rng(9)
docs = [rng.integers(0, k, size=L).astype(np.int32)
        for L in [40] * 8 + [67] * 4]
plan = ScanPlan(mode="enumeration", chunking=ChunkPolicy(n_chunks=8))
local = Scanner.compile(dfas, plan)
dist = Scanner.compile(dfas, plan.with_(distribution="shard_map"))
assert jax.device_count() == 4 and dist.mesh.size == 4
assert set(dist.pattern_modes.values()) == {"enumeration"}
assert np.array_equal(local.scan(docs).hits, dist.scan(docs).hits)
print("OK")
"""


def test_shard_map_enumeration_on_four_devices_matches_local():
    """The shard_map path runs the same executor on each device's doc
    shard (4 virtual CPU devices in a child process)."""
    src = Path(__file__).resolve().parents[1] / "src"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(src)}
    r = subprocess.run([sys.executable, "-c", _SHARD_MAP_SCRIPT],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().splitlines()[-1] == "OK"
