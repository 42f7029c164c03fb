"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import inspect

import jax.numpy as jnp
import numpy as np
import pytest
from _strategies import given, settings, st

from repro.core.fingerprint import BarrettConstants
from repro.kernels import ops, ref

CONSTS = BarrettConstants.create()
RNG = np.random.default_rng(0)


@pytest.mark.parametrize("B,W", [(1, 1), (3, 2), (8, 5), (17, 9), (64, 32), (5, 47)])
def test_fingerprint_kernel_matches_ref(B, W):
    words = jnp.asarray(
        RNG.integers(0, 1 << 32, size=(B, W), dtype=np.uint64).astype(np.uint32)
    )
    got = ops.fingerprint(words, CONSTS, block_b=8, interpret=True)
    want = ref.fingerprint_ref(words, CONSTS)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block_b", [1, 4, 16, 256])
def test_fingerprint_kernel_block_sizes(block_b):
    words = jnp.asarray(
        RNG.integers(0, 1 << 32, size=(13, 7), dtype=np.uint64).astype(np.uint32)
    )
    got = ops.fingerprint(words, CONSTS, block_b=block_b, interpret=True)
    want = ref.fingerprint_ref(words, CONSTS)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("B,n", [(1, 4), (3, 16), (5, 50), (2, 130), (1, 257)])
def test_compose_kernel_matches_gather(B, n):
    f = jnp.asarray(RNG.integers(0, n, size=(B, n)).astype(np.int32))
    g = jnp.asarray(RNG.integers(0, n, size=(B, n)).astype(np.int32))
    got = ops.compose(f, g, block_q=32, interpret=True)
    want = ref.compose_ref(f, g)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(min_value=2, max_value=24),
    seed=st.integers(min_value=0, max_value=999),
)
def test_compose_kernel_is_composition(n, seed):
    rng = np.random.default_rng(seed)
    f = rng.integers(0, n, size=(2, n)).astype(np.int32)
    g = rng.integers(0, n, size=(2, n)).astype(np.int32)
    got = np.asarray(ops.compose(jnp.asarray(f), jnp.asarray(g), interpret=True))
    for b in range(2):
        for q in range(n):
            assert got[b, q] == g[b, f[b, q]]


@pytest.mark.parametrize("n,k,B,L", [(3, 4, 2, 5), (6, 5, 3, 8), (16, 20, 2, 12), (31, 7, 1, 9)])
def test_match_kernel_matches_ref(n, k, B, L):
    table = jnp.asarray(RNG.integers(0, n, size=(n, k)).astype(np.int32))
    chunks = jnp.asarray(RNG.integers(0, k, size=(B, L)).astype(np.int32))
    got = ops.match_chunks(table, chunks, interpret=True)
    want = ref.match_chunks_ref(table, chunks)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_match_kernel_composes_with_compose_kernel():
    """match(chunk1+chunk2) == compose(match(chunk1), match(chunk2)) — the
    paper's chunk-combine property, on the kernels themselves."""
    n, k, L = 8, 5, 6
    table = jnp.asarray(RNG.integers(0, n, size=(n, k)).astype(np.int32))
    c1 = RNG.integers(0, k, size=(1, L)).astype(np.int32)
    c2 = RNG.integers(0, k, size=(1, L)).astype(np.int32)
    m1 = ops.match_chunks(table, jnp.asarray(c1), interpret=True)
    m2 = ops.match_chunks(table, jnp.asarray(c2), interpret=True)
    whole = ops.match_chunks(
        table, jnp.asarray(np.concatenate([c1, c2], axis=1)), interpret=True
    )
    composed = ops.compose(m1, m2, interpret=True)
    assert np.array_equal(np.asarray(whole), np.asarray(composed))


@pytest.mark.parametrize("name", [
    "clmul.fingerprint_bank_pallas", "clmul.fingerprint_pallas",
    "compose.compose_pallas", "expand.expand_bank_pallas",
    "match_scan.match_bank_chunks_pallas", "match_scan.match_chunks_pallas",
])
def test_pallas_entry_points_compile_unless_asked(name):
    """No kernel entry point interprets unless its caller asks."""
    import importlib

    mod, fn = name.split(".")
    f = getattr(importlib.import_module(f"repro.kernels.{mod}"), fn)
    assert inspect.signature(f).parameters["interpret"].default is False


@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None),
])
def test_default_interpret_only_on_cpu(monkeypatch, platform, interpret):
    """The backend probe interprets on "cpu", compiles on "tpu", and refuses
    any other platform instead of silently interpreting there."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
    ops._default_interpret.cache_clear()
    try:
        if interpret is None:
            with pytest.raises(RuntimeError):
                ops._default_interpret()
        else:
            assert ops._default_interpret() is interpret
    finally:
        ops._default_interpret.cache_clear()
