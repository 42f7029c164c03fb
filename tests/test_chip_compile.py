"""Compile rehearsal: the main path's Pallas kernels and the fused
construction round, compiled for a TPU v5e that is described, not attached.

Interpret mode cannot see what the chip's compiler refuses (a reduction it
does not lower, a block shape off the (8, 128) tiling, a dynamic slice of a
value); these compiles can, at the shapes the scan service really runs:
the 23-signature bundled bank (n_max = 87, k = 20), construction tiles of
128 frontier rows, and 8,192 chunks of 46 symbols. Every kernel test
asserts the compiled program holds a Mosaic kernel (``tpu_custom_call``),
i.e. the kernel was compiled, not interpreted. The XLA enumeration
executor compiles at the batch cell's shard shapes with no gather in its
per-symbol loop.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

I32, U32 = jnp.int32, jnp.uint32
TILE, N, K = 128, 87, 20           # construction tile, bundled n_max, |Σ|


@pytest.fixture(scope="module")
def one_chip():
    """A single-device sharding on a described v5e:2x2, with the persistent
    compilation cache off (a compile for a described chip is written to the
    cache but cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_fingerprint_bank_compiles_for_v5e(one_chip):
    from repro.kernels.clmul import fingerprint_bank_pallas

    P, W = 16, (N + 1) // 2
    _compile(functools.partial(fingerprint_bank_pallas, interpret=False),
             one_chip, ((P, TILE * K, W), U32), ((P, W, 2), U32),
             ((P, 4), U32))


def test_expand_bank_compiles_for_v5e(one_chip):
    from repro.kernels.expand import expand_bank_pallas

    _compile(functools.partial(expand_bank_pallas, interpret=False),
             one_chip, ((16, N, K), I32), ((16, TILE, N), I32))


@pytest.mark.parametrize("P,n,starts", [(23, N, None), (18, 272, 1)])
def test_match_bank_chunks_compiles_for_v5e(one_chip, P, n, starts):
    """Enumeration tables (every start state) and stacked SFA deltas (from
    state 0 only), both over 8,192 chunks of 46 symbols."""
    from repro.kernels.match_scan import match_bank_chunks_pallas

    _compile(functools.partial(match_bank_chunks_pallas, starts=starts,
                               interpret=False),
             one_chip, ((P, n, K), I32), ((8192, 46), I32))


def test_compose_compiles_for_v5e(one_chip):
    from repro.kernels.compose import compose_pallas

    _compile(functools.partial(compose_pallas, interpret=False),
             one_chip, ((64, 512), I32), ((64, 512), I32))


def test_construction_round_compiles_for_v5e(one_chip):
    """The fused local round (gather, Pallas expansion, Pallas fingerprint,
    sort-merge, scatter) at the bundled bank's widest bucket."""
    from repro.construction.batched import _make_local_step, round_schedule

    P = 23
    sched = round_schedule(tile=TILE, n=N, k=K, max_states=512, P=P)
    cap, bucket = sched.capacities[0], sched.buckets[-1]
    step = _make_local_step(tile=TILE, n=N, k=K, capacity=cap, P=P,
                            bucket=bucket, fp_backend="pallas",
                            expand_backend="pallas", interpret=False)
    W = (N + 1) // 2
    _compile(step, one_chip,
             ((P, N, K), I32), ((P, cap, N), I32), ((P, cap), U32),
             ((P, cap), U32), ((P, cap, K), I32), ((P,), I32), ((P,), I32),
             ((P, W, 2), U32), ((P, 4), U32), ((P, W), U32),
             ((bucket,), I32), ((bucket,), jnp.bool_))


@pytest.mark.parametrize("Pg,n", [(2, 57), (1, 87)])
def test_enumeration_executor_compiles_for_v5e(one_chip, Pg, n):
    """``bank_doc_mappings`` for a 1,024 x 448 shard (8,192 chunks of 56
    symbols) at the batch cell's enumeration groups: the loop that carries
    the (n, lanes / 128, 128) tracked states holds no gather."""
    from _hlo import loop_body
    from repro.engine.executors import bank_doc_mappings

    args = [jax.ShapeDtypeStruct(s, I32, sharding=one_chip)
            for s in ((Pg, n, K), (1024, 448))]
    text = bank_doc_mappings.lower(*args, 8).compile().as_text()
    body = loop_body(text, f"s32[{n},{Pg * 8192 // 128},128]")
    assert body and not [ln for ln in body if "gather(" in ln]
