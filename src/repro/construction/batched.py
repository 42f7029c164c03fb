"""Batched SFA construction: every pattern's frontier advances at once.

The paper's headline result is *construction* speed through task-level
parallelism: hundreds of PROSITE signatures, each an independent worklist
closure. This module expresses that task parallelism as a batch dimension.
``construct_bank`` pads ``P`` DFAs to a common state count (the
``PatternBank`` self-loop/identity padding story) and advances **all P
frontiers simultaneously** in one compiled bulk-synchronous round over
stacked ``(P, capacity, n_max)`` state buffers:

  1. each pattern slices a ``tile`` of unprocessed frontier states;
  2. frontier × alphabet expands in one fused gather per pattern (vmapped);
  3. candidates are fingerprinted with *per-pattern* fold constants — a
     per-pattern word mask zeroes the padding tail, so the fingerprints (and
     therefore the whole discovery sequence) are bit-identical to the
     unpadded per-pattern engines. The fingerprint stage is plan-selectable:
     the fused-XLA clmul fold, or the ``kernels.clmul`` Pallas bank kernel
     (bit-identical; the natural pick on a TPU runtime);
  4. membership is the sort-merge of (known ∪ candidates) fingerprints, per
     pattern, looped over the bank by ``lax.map`` inside the same XLA
     program (a batched multi-key sort compiles for minutes on a TPU);
  5. per-pattern ``done`` / ``blowup`` / ``collision`` flags come back each
     round. Collided patterns restart alone with the next irreducible
     polynomial (per-pattern retry, applied as one batched scatter: the
     other patterns keep their progress); finished or blown patterns are
     *compacted out* of later rounds on the host — the paper's nonblocking
     construction: no pattern waits on a straggler's barrier.

**Every shape a construction can visit is known before the first round.**
:func:`round_schedule` precomputes the capacity tiers (geometric growth
toward the ``n^n``/budget cap) and active-set buckets (geometric shrink from
``P``) from ``(tile, n, k, max_states, P, quantum)`` alone; the host loop
only ever selects shapes from that schedule. Each selected shape's round —
the *whole* round: pattern gather, frontier expansion, fingerprints,
sort-merge, scatter-back — is one AOT-compiled executable cached in the
process-wide :func:`~repro.construction.cache.round_compile_cache`, so a
repeat ``construct_bank`` of a previously-seen shape performs **zero new
traces and zero new XLA compiles** (asserted by the compile-count
regression tests).

``distribution="shard_map"`` shards the pattern axis of every buffer across
the devices of a mesh, one bank shard per device, with the same host loop
driving all shards — the multicore experiment of the paper at pod scale.

The single-pattern jitted engine (``construct_sfa_jax``, formerly
``core/sfa_jax.py``) is the ``P = 1`` special case of the same round.
"""

from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PSpec

from .. import obs
from ..compat import make_mesh, shard_map as compat_shard_map
from ..core.dfa import DFA
from ..core.fingerprint import (
    BarrettConstants,
    clmul32,
    clmul64,
    fingerprint_states_np,
    fold_weights_u32,
    nth_poly_low,
    pack_states_u32,
)
from ..core.multipattern import PatternBank
from .cache import round_compile_cache
from .types import (
    BankConstructionResult,
    BankStats,
    BucketStats,
    SFA,
    SFAStats,
    FingerprintCollision,
    StateBlowup,
)

_U32MAX = jnp.uint32(0xFFFFFFFF)

# /metrics HELP descriptions, registered once; callsites publish by name.
obs.counter("construction.banks", help="construct_bank calls completed")
obs.counter("construction.patterns", help="patterns constructed in banks")
obs.counter("construction.rounds", help="batched construction rounds run")
obs.counter("construction.retries",
            help="per-pattern fingerprint-collision retries")
obs.counter("construction.blown",
            help="patterns abandoned to the state-budget blowup verdict")
obs.histogram("construction.bank_wall_s",
              help="construct_bank wall seconds per bank")
obs.histogram("construction.round_wall_s",
              help="wall seconds per batched construction round")

#: Fingerprint-stage backends of the batched round. ``"auto"`` resolves to
#: ``"pallas"`` on a real TPU runtime and ``"xla"`` elsewhere (interpret-mode
#: Pallas would dominate a CPU round).
FINGERPRINT_BACKENDS = ("auto", "xla", "pallas")

#: Expansion-stage backends of the batched round: ``"xla"`` is the fused
#: ``jnp.take`` gather, ``"pallas"`` the one-hot MXU gather kernel
#: (``kernels.ops.expand_frontier_bank``, bit-identical), ``"auto"`` picks
#: pallas on a TPU runtime and xla elsewhere.
EXPAND_BACKENDS = ("auto", "xla", "pallas")

#: Size-bucketing modes of ``construct_bank``. ``"size"`` always partitions
#: the bank by DFA state count, ``"off"`` never does, ``"auto"`` partitions
#: when the bank is big and skewed enough for bucketing to pay (at least
#: ``_BUCKET_AUTO_MIN_P`` patterns spreading over >= 2 merged buckets).
BUCKETINGS = ("auto", "size", "off")

#: Buckets smaller than this merge into a neighbor: a compiled round shape
#: has to amortize over enough patterns to beat riding along padded.
_BUCKET_MIN_PATTERNS = 4

#: ``bucketing="auto"`` leaves banks smaller than this unbucketed — the
#: round dispatch overhead of extra sub-banks outweighs padding savings.
_BUCKET_AUTO_MIN_P = 8

#: Capacity tiers grow by this factor between schedule entries. Fixed (not a
#: knob): fewer, coarser tiers mean fewer compiled shapes, and results are
#: capacity-invariant anyway (pinned by the capacity-growth bit-exactness
#: test).
CAPACITY_GROWTH = 4


# --------------------------------------------------------------------------
# The round, in stages (each stage batched over the pattern axis)
# --------------------------------------------------------------------------


def _fold_words(words, weights, limbs):
    """Fold + Barrett-reduce packed (B, W) words with one pattern's
    constants -> (B, 2) uint32 [hi, lo]. The reference fingerprint stage
    (fused XLA clmul); ``kernels.clmul.fingerprint_bank_pallas`` computes
    the identical function as a Pallas kernel."""
    wh = weights[: words.shape[-1], 0]
    wl = weights[: words.shape[-1], 1]
    p_lo_h, p_lo_l = clmul32(words, wl)
    p_hi_h, p_hi_l = clmul32(words, wh)

    def xred(x):
        return jax.lax.reduce(
            x, jnp.zeros((), x.dtype), jax.lax.bitwise_xor, (x.ndim - 1,)
        )

    l0 = xred(p_lo_l)
    l1 = xred(p_lo_h ^ p_hi_l)
    l2 = xred(p_hi_h)
    t1pre = (jnp.zeros_like(l2), l2)
    m3, m2, _, _ = clmul64(t1pre, (limbs[2], limbs[3]))  # × mu
    t2pre = (t1pre[0] ^ m3, t1pre[1] ^ m2)
    _, _, q1, q0 = clmul64(t2pre, (limbs[0], limbs[1]))  # × p
    return jnp.stack([l1 ^ q1, l0 ^ q0], axis=-1)


def _masked_fingerprint(states, weights, word_mask, limbs):
    """Fingerprint padded state vectors with per-pattern constants.

    ``word_mask`` zeroes the packed words of the identity padding tail, so
    the result equals the fingerprint of the *unpadded* vector — the bit
    that keeps batched construction bit-identical to the per-pattern
    engines. ``limbs`` are the Barrett constants as traced u32 scalars
    [p_hi, p_lo, mu_hi, mu_lo].
    """
    words = pack_states_u32(states) & word_mask[None, :]
    return _fold_words(words, weights, limbs)


def _frontier_tile(
    states_buf,       # (C, n) int32
    n_states,         # () int32
    frontier_lo,      # () int32
    active,           # () bool — this pattern still advancing
    *, tile: int, n: int,
):
    """Stage 1: slice the frontier tile and its row-validity mask.
    -> (ft (T, n), row_valid (T,))."""
    ft = jax.lax.dynamic_slice(states_buf, (frontier_lo, 0), (tile, n))
    row_ids = frontier_lo + jnp.arange(tile, dtype=jnp.int32)
    row_valid = (row_ids < n_states) & active            # (T,)
    return ft, row_valid


def _gather_expand(table, ft, *, tile: int, n: int, k: int):
    """Stage 2, XLA backend: expand frontier × alphabet in one fused gather.
    ``next[f, a, q] = δ(f[q], a)``, symbol axis materialized, row-major
    (frontier, symbol) candidate order. -> (T·k, n)."""
    cand = jnp.take(table, ft, axis=0)                   # (T, n, k)
    return jnp.swapaxes(cand, 1, 2).reshape(tile * k, n)


def _merge(
    states_buf,       # (C, n) int32
    fp_hi, fp_lo,     # (C,) uint32
    delta_buf,        # (C, k) int32
    n_states,         # () int32
    frontier_lo,      # () int32
    active,           # () bool
    cand,             # (T·k, n) int32
    cand_valid,       # (T·k,) bool
    c_hi, c_lo,       # (T·k,) uint32 candidate fingerprints
    *, tile: int, n: int, k: int, capacity: int,
):
    """Stages 4/5: sort-merge membership, exactness check, state append and
    δ_s rows — one pattern; mapped over the bank axis."""
    C = capacity
    total = C + tile * k
    known_valid = jnp.arange(C, dtype=jnp.int32) < n_states
    inval = jnp.concatenate([(~known_valid), (~cand_valid)]).astype(jnp.uint32)
    hi = jnp.concatenate([fp_hi, c_hi])
    lo = jnp.concatenate([fp_lo, c_lo])
    is_cand = jnp.concatenate(
        [jnp.zeros(C, jnp.uint32), jnp.ones(tile * k, jnp.uint32)]
    )
    payload = jnp.concatenate(
        [jnp.arange(C, dtype=jnp.int32), jnp.arange(tile * k, dtype=jnp.int32)]
    )
    # Sort by (validity, fp_hi, fp_lo, known<cand, original index).
    tie = payload.astype(jnp.uint32)
    s_inval, s_hi, s_lo, s_isc, s_tie, s_pay = jax.lax.sort(
        (inval, hi, lo, is_cand, tie, payload), num_keys=5
    )

    run_start = jnp.concatenate(
        [jnp.ones(1, bool),
         (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1])
         | (s_inval[1:] != s_inval[:-1])]
    )
    pos = jnp.arange(total, dtype=jnp.int32)
    head_pos = jax.lax.cummax(jnp.where(run_start, pos, -1), axis=0)
    head_pay = s_pay[head_pos]
    head_is_known = s_isc[head_pos] == 0

    # New-state heads: candidate-headed runs that are valid.
    s_valid = s_inval == 0
    is_new_head = run_start & (s_isc == 1) & s_valid
    # Rank new heads by original candidate index -> BFS discovery order.
    rank_key = jnp.where(is_new_head, s_pay, jnp.int32(2**31 - 1))
    order = jnp.argsort(rank_key)
    ranks = jnp.zeros(total, jnp.int32).at[order].set(
        jnp.arange(total, dtype=jnp.int32)
    )
    new_id_at_pos = n_states + ranks                     # valid where is_new_head

    # id of each sorted position = head's id.
    head_new_id = new_id_at_pos[head_pos]
    id_sorted = jnp.where(head_is_known, head_pay, head_new_id)

    # Exactness check (candidates vs run-head vectors).
    cand_rows = s_isc == 1
    ref_known = states_buf[jnp.clip(head_pay, 0, C - 1)]
    ref_cand = cand[jnp.clip(head_pay, 0, tile * k - 1)]
    ref_vec = jnp.where(head_is_known[:, None], ref_known, ref_cand)
    own_vec = cand[jnp.clip(s_pay, 0, tile * k - 1)]
    mismatch = jnp.any(ref_vec != own_vec, axis=1) & cand_rows & s_valid
    collision = jnp.any(mismatch)

    # Append new states.
    num_new = jnp.sum(is_new_head.astype(jnp.int32))
    tgt = jnp.where(is_new_head, new_id_at_pos, C)       # C = out-of-range drop
    src_vec = cand[jnp.clip(s_pay, 0, tile * k - 1)]
    states_buf = states_buf.at[tgt].set(src_vec, mode="drop")
    fp_hi = fp_hi.at[tgt].set(s_hi, mode="drop")
    fp_lo = fp_lo.at[tgt].set(s_lo, mode="drop")

    # Write δ_s rows for the tile: candidate (f, a) order is row-major, so
    # candidate ids scattered back to original order reshape into delta rows.
    ids_orig = jnp.zeros(tile * k, jnp.int32).at[
        jnp.where(cand_rows, s_pay, tile * k)
    ].set(id_sorted, mode="drop")
    delta_rows = ids_orig.reshape(tile, k)
    delta_buf = jax.lax.dynamic_update_slice(
        delta_buf, delta_rows, (frontier_lo, 0)
    )

    processed = jnp.where(
        active, jnp.minimum(n_states - frontier_lo, tile), 0
    )
    return (
        states_buf, fp_hi, fp_lo, delta_buf,
        n_states + num_new, frontier_lo + processed, collision,
    )


def _bucket_round(tables, states, fp_hi, fp_lo, delta, n_states, frontier,
                  active, weights, limbs, word_masks,
                  *, tile: int, n: int, k: int, capacity: int,
                  fp_backend: str, expand_backend: str, interpret: bool):
    """One bulk-synchronous round over a bucket of patterns: expand
    (selected backend), then fingerprint (selected backend), then
    sort-merge — stages 1–5 above, batched over the bucket axis."""
    tiler = functools.partial(_frontier_tile, tile=tile, n=n)
    ft, row_valid = jax.vmap(tiler)(states, n_states, frontier, active)
    if expand_backend == "pallas":
        from ..kernels import ops as kernel_ops

        cand = kernel_ops.expand_frontier_bank(
            tables, ft, interpret=interpret
        )                                                    # (B, T·k, n)
    else:
        gather = functools.partial(_gather_expand, tile=tile, n=n, k=k)
        cand = jax.vmap(gather)(tables, ft)
    cand_valid = jnp.repeat(row_valid, k, axis=1)            # (B, T·k)
    words = pack_states_u32(cand) & word_masks[:, None, :]   # (B, T·k, W)
    if fp_backend == "pallas":
        from ..kernels import ops as kernel_ops

        fp = kernel_ops.fingerprint_bank_stacked(
            words, weights, limbs, interpret=interpret
        )
    else:
        fp = jax.vmap(_fold_words)(words, weights, limbs)
    merge = functools.partial(_merge, tile=tile, n=n, k=k, capacity=capacity)
    # One pattern at a time (``lax.map``), not ``vmap``: the TPU compiler
    # takes over a minute for the batched multi-key sort at bucket size 23
    # and a few seconds for the 1-D sort inside a loop.
    return jax.lax.map(lambda args: merge(*args), (
        states, fp_hi, fp_lo, delta, n_states, frontier, active,
        cand, cand_valid, fp[..., 0], fp[..., 1],
    ))


# --------------------------------------------------------------------------
# Compiled round steps (AOT, cached process-wide)
# --------------------------------------------------------------------------


def _make_local_step(*, tile, n, k, capacity, P, bucket, fp_backend,
                     expand_backend, interpret):
    """The whole local round as ONE function of the full-size bank buffers:
    gather the active bucket, run the round, scatter the bucket back. AOT
    compiling *this* (rather than only the vmapped round) keeps the host
    loop free of per-round eager gather/scatter dispatches — those small ops
    were half the cold-start compile wall."""

    def step(tables, states, fp_hi, fp_lo, delta, n_states, frontier,
             weights, limbs, word_masks, idx, act):
        def take(a):
            return jnp.take(a, idx, axis=0)

        o_states, o_fp_hi, o_fp_lo, o_delta, o_n, o_frontier, o_coll = (
            _bucket_round(
                take(tables), take(states), take(fp_hi), take(fp_lo),
                take(delta), take(n_states), take(frontier), act,
                take(weights), take(limbs), take(word_masks),
                tile=tile, n=n, k=k, capacity=capacity,
                fp_backend=fp_backend, expand_backend=expand_backend,
                interpret=interpret,
            )
        )
        # ``idx`` pads the bucket tail with duplicates of its first entry;
        # route inactive rows out of range so the scatter targets are unique
        # and padding never writes.
        sidx = jnp.where(act, idx, jnp.int32(P))
        return (
            states.at[sidx].set(o_states, mode="drop"),
            fp_hi.at[sidx].set(o_fp_hi, mode="drop"),
            fp_lo.at[sidx].set(o_fp_lo, mode="drop"),
            delta.at[sidx].set(o_delta, mode="drop"),
            n_states.at[sidx].set(o_n, mode="drop"),
            frontier.at[sidx].set(o_frontier, mode="drop"),
            o_coll & act,
        )

    return step


def _local_step_exe(*, tile, n, k, capacity, P, bucket, fp_backend,
                    expand_backend, interpret):
    """AOT executable of the fused local step for one schedule shape,
    through the process-wide :func:`round_compile_cache`."""
    key = ("local-step", tile, n, k, capacity, P, bucket, fp_backend,
           expand_backend, interpret)

    def build():
        step = _make_local_step(
            tile=tile, n=n, k=k, capacity=capacity, P=P, bucket=bucket,
            fp_backend=fp_backend, expand_backend=expand_backend,
            interpret=interpret,
        )
        W = (n + 1) // 2
        s = jax.ShapeDtypeStruct
        i32, u32 = jnp.int32, jnp.uint32
        avals = (
            s((P, n, k), i32),            # tables
            s((P, capacity, n), i32),     # states
            s((P, capacity), u32),        # fp_hi
            s((P, capacity), u32),        # fp_lo
            s((P, capacity, k), i32),     # delta
            s((P,), i32),                 # n_states
            s((P,), i32),                 # frontier
            s((P, W, 2), u32),            # weights
            s((P, 4), u32),               # limbs
            s((P, W), u32),               # word masks
            s((bucket,), i32),            # idx
            s((bucket,), jnp.bool_),      # act
        )
        return jax.jit(step).lower(*avals).compile()

    return round_compile_cache().get(key, build)


def _sharded_round_exe(mesh, pattern_axis: str, *, tile, n, k, capacity,
                       fp_backend, expand_backend, interpret):
    """shard_map wrapper of the bucket round: every buffer's pattern axis
    shards over ``pattern_axis``; each device closes its bank shard. Cached
    as a jitted callable (jit's own cache keys the per-bucket shapes), so
    repeat constructions reuse both the wrapper and its compiled shapes."""
    key = ("shard-round", mesh, pattern_axis, tile, n, k, capacity,
           fp_backend, expand_backend, interpret)

    def build():
        def local(*args):
            return _bucket_round(
                *args, tile=tile, n=n, k=k, capacity=capacity,
                fp_backend=fp_backend, expand_backend=expand_backend,
                interpret=interpret,
            )

        @jax.jit
        def rounds(*args):
            fn = compat_shard_map(
                local,
                mesh=mesh,
                in_specs=tuple(PSpec(pattern_axis) for _ in range(11)),
                out_specs=tuple(PSpec(pattern_axis) for _ in range(7)),
                check_vma=False,
            )
            return fn(*args)

        return rounds

    return round_compile_cache().get(key, build)


# --------------------------------------------------------------------------
# The fixed shape schedule
# --------------------------------------------------------------------------


def _state_cap(n: int, max_states: int) -> int:
    """min(max_states, n^n): the SFA can never exceed n^n mappings, so small
    automata get small buffers even under a huge budget."""
    if n <= 1:
        return 1
    if n * math.log2(n) <= 40:
        return min(max_states, n ** n)
    return max_states


def _bucket_sizes(P: int, quantum: int, growth: int = 4) -> list:
    """Active-set padding buckets: shrinking by ``growth`` from P, rounded up
    to multiples of ``quantum`` (the mesh's pattern-axis size) — O(log P)
    compiled shapes."""

    def up(x):
        return max(quantum, ((x + quantum - 1) // quantum) * quantum)

    sizes, b = [], up(P)
    while True:
        sizes.append(b)
        if b == up(1):
            break
        b = up((b + growth - 1) // growth)
    return sorted(set(sizes))


@dataclass(frozen=True)
class RoundSchedule:
    """Every (capacity, bucket) round shape one bank construction may visit,
    precomputed from static quantities — no runtime value can produce a
    shape outside this set, which is what makes the AOT compile cache's
    "zero new traces on repeat" guarantee possible.

    ``capacities`` are the buffer-row tiers (ascending, last = full cap);
    ``buckets`` the active-set padding sizes (ascending, ``quantum``-rounded
    for the mesh's pattern axis).
    """

    tile: int
    n: int
    k: int
    P: int
    quantum: int
    capacities: tuple
    buckets: tuple

    def capacity_for(self, worst: int) -> int:
        """Smallest tier holding ``worst`` rows (or the full cap)."""
        for c in self.capacities:
            if c >= worst:
                return c
        return self.capacities[-1]

    def bucket_for(self, n_active: int) -> int:
        """Smallest bucket holding ``n_active`` patterns."""
        for b in self.buckets:
            if b >= n_active:
                return b
        return self.buckets[-1]

    @property
    def shapes(self) -> tuple:
        """The full (capacity, bucket) cross product — the upper bound on
        compiled round programs for this bank."""
        return tuple((c, b) for c in self.capacities for b in self.buckets)


def round_schedule(*, tile: int, n: int, k: int, max_states: int, P: int,
                   quantum: int = 1,
                   bucket_growth: int = 4) -> RoundSchedule:
    """Precompute the capacity/bucket schedule of a bank construction.

    Capacity starts small (a 200k-state budget must not mean 200k-row sorts
    for a bank that closes in a few hundred states) and grows by
    ``CAPACITY_GROWTH`` toward ``n^n``/budget; buckets shrink by
    ``bucket_growth`` from ``P``. The host loop's growth guard keeps
    ``capacity >= n_states + tile·k`` for every runnable pattern, so a round
    can never drop an append of a pattern that still fits the cap.
    """
    if bucket_growth < 2:
        raise ValueError(f"bucket_growth must be >= 2, got {bucket_growth}")
    full_cap = _state_cap(n, max_states) + tile
    caps = [min(full_cap, max(1024, 2 * (tile * k + tile)))]
    while caps[-1] < full_cap:
        caps.append(min(full_cap, caps[-1] * CAPACITY_GROWTH))
    return RoundSchedule(
        tile=tile, n=n, k=k, P=P, quantum=quantum,
        capacities=tuple(caps),
        buckets=tuple(_bucket_sizes(P, quantum, bucket_growth)),
    )


def _resolve_fp_backend(backend: str) -> str:
    if backend not in FINGERPRINT_BACKENDS:
        raise ValueError(
            f"fingerprint_backend must be one of {FINGERPRINT_BACKENDS}, "
            f"got {backend!r}"
        )
    if backend == "auto":
        from ..kernels import ops as kernel_ops

        return "xla" if kernel_ops._default_interpret() else "pallas"
    return backend


def _resolve_expand_backend(backend: str) -> str:
    if backend not in EXPAND_BACKENDS:
        raise ValueError(
            f"expand_backend must be one of {EXPAND_BACKENDS}, "
            f"got {backend!r}"
        )
    if backend == "auto":
        from ..kernels import ops as kernel_ops

        return "xla" if kernel_ops._default_interpret() else "pallas"
    return backend


# --------------------------------------------------------------------------
# Host-side bank driver
# --------------------------------------------------------------------------


def _word_mask(n_true: int, n_pad: int) -> np.ndarray:
    """Packed-word mask selecting the unpadded prefix of a padded vector."""
    W = (n_pad + 1) // 2
    m = np.zeros(W, dtype=np.uint32)
    m[: n_true // 2] = np.uint32(0xFFFFFFFF)
    if n_true % 2:
        m[n_true // 2] = np.uint32(0x0000FFFF)
    return m


def _default_weight_fn(pattern: int, attempt: int, n_words: int,
                       consts: BarrettConstants) -> np.ndarray:
    return np.asarray(fold_weights_u32(n_words, consts))


@functools.lru_cache(maxsize=8192)
def _seed_fingerprint(n_true: int, poly_low: int) -> tuple:
    """(hi, lo) fingerprint of the identity mapping over ``n_true`` states —
    the bank's seed row. Pure function of (size, polynomial), so it is
    cached: warm re-constructions and same-size patterns inside one bank
    skip the host-side Barrett fold entirely."""
    c = BarrettConstants.cached(poly_low)
    fp = fingerprint_states_np(np.arange(n_true, dtype=np.int32)[None], c)[0]
    return int(fp[0]), int(fp[1])


def _limbs_of(consts: BarrettConstants) -> np.ndarray:
    return np.asarray(
        [
            (consts.poly_low >> 32) & 0xFFFFFFFF,
            consts.poly_low & 0xFFFFFFFF,
            (consts.mu_low >> 32) & 0xFFFFFFFF,
            consts.mu_low & 0xFFFFFFFF,
        ],
        dtype=np.uint32,
    )


def construct_bank(
    dfas: Sequence[DFA] | PatternBank,
    *,
    max_states: int = 200_000,
    tile: int = 128,
    max_retries: int = 4,
    poly_index: int = 0,
    method: str = "batched",
    engine: str = "vectorized",
    distribution: str = "local",
    mesh=None,
    pattern_axis: str = "pattern",
    on_blowup: str = "skip",
    fingerprint_backend: str = "auto",
    expand_backend: str = "auto",
    bucketing: str = "auto",
    bucket_growth: int = 4,
    _weight_fn=None,
) -> BankConstructionResult:
    """Construct the exact SFA of every pattern in one batched closure.

    ``method="batched"`` runs the compiled bulk-synchronous bank rounds
    above; ``method="loop"`` is the sequential-loop baseline (per-pattern
    :func:`~repro.construction.construct_sfa` with ``engine=``), kept for
    benchmarking and as the cheap path when only one pattern misses the
    cache. Both return bit-identical SFAs.

    ``on_blowup``: ``"skip"`` marks patterns whose closure exceeds
    ``max_states`` in ``result.blown`` (their slot in ``sfas`` is ``None``);
    ``"raise"`` raises :class:`StateBlowup` instead.

    ``distribution="shard_map"`` (batched method only) shards the pattern
    axis of every buffer over ``mesh`` (default: a fresh 1-axis mesh over
    all devices named ``pattern_axis``).

    ``fingerprint_backend`` picks the round's fingerprint stage: ``"xla"``
    (fused clmul fold), ``"pallas"`` (the ``kernels.ops.fingerprint_bank``
    Rabin kernel — bit-identical), or ``"auto"`` (pallas on a TPU runtime,
    xla elsewhere). ``expand_backend`` picks the frontier-expansion stage
    the same way (``"xla"`` = fused ``jnp.take`` gather, ``"pallas"`` = the
    ``kernels.ops.expand_frontier_bank`` one-hot MXU gather, bit-identical).
    ``bucket_growth`` sets the active-set bucket shrink factor of the shape
    schedule (see :func:`round_schedule`): larger means fewer compiled
    shapes, at the cost of more padding in mid-size rounds.

    ``bucketing`` controls *size*-bucketed construction: one padded bank
    charges every pattern ``n_max``-wide frontier rows, fingerprint words,
    and full-capacity sort-merges, so size-skewed banks (the P=64 regime)
    pay mostly for padding. ``"size"`` partitions the bank by DFA state
    count into O(log n_max) sub-banks (``core.bucketing`` geometric edges,
    small buckets merged away), each constructed with bucket-local
    ``n_max``/capacity tiers through the same AOT round cache; ``"off"``
    keeps one bank; ``"auto"`` buckets only when the bank is big and skewed
    enough to pay (>= 2 merged buckets over >= 8 patterns). Results are
    bit-identical across all three — per-pattern word masks already make
    fingerprints padding-invariant.

    ``_weight_fn(pattern, attempt, n_words, consts)`` is a test seam: it
    supplies the fingerprint fold constants and lets tests force a
    fingerprint collision for one pattern's first attempt.
    """
    if isinstance(dfas, PatternBank):
        dfas = [dfas.dfa(p) for p in range(dfas.n_patterns)]
    dfas = list(dfas)
    if not dfas:
        raise ValueError("empty pattern bank")
    if method not in ("batched", "loop"):
        raise ValueError(f"method must be 'batched' or 'loop', got {method!r}")
    fp_backend = _resolve_fp_backend(fingerprint_backend)
    exp_backend = _resolve_expand_backend(expand_backend)
    if bucketing not in BUCKETINGS:
        raise ValueError(
            f"bucketing must be one of {BUCKETINGS}, got {bucketing!r}"
        )
    if bucket_growth < 2:
        raise ValueError(f"bucket_growth must be >= 2, got {bucket_growth}")

    with obs.span("construct_bank", patterns=len(dfas), method=method,
                  bucketing=bucketing):
        if method == "loop":
            result = _construct_loop(
                dfas, max_states=max_states, max_retries=max_retries,
                engine=engine, poly_index=poly_index,
            )
        else:
            result = _construct_bucketed(
                dfas, max_states=max_states, tile=tile,
                max_retries=max_retries,
                poly_index=poly_index, distribution=distribution, mesh=mesh,
                pattern_axis=pattern_axis, fp_backend=fp_backend,
                expand_backend=exp_backend, bucketing=bucketing,
                bucket_growth=bucket_growth,
                weight_fn=_weight_fn or _default_weight_fn,
            )
    obs.counter("construction.banks").inc()
    obs.counter("construction.patterns").inc(len(dfas))
    obs.counter("construction.rounds").inc(result.stats.rounds)
    obs.counter("construction.retries").inc(int(result.stats.retries.sum()))
    obs.counter("construction.blown").inc(int(result.blown.sum()))
    obs.histogram("construction.bank_wall_s").observe(result.stats.wall_time_s)
    if on_blowup == "raise":
        result.require_all()
    return result


def _construction_partition(sizes, bucketing: str):
    """The size-bucket partition of one bank, or ``None`` to run unbucketed.
    -> ``[(edge, [pattern indices…]), …]`` via the shared
    :mod:`repro.core.bucketing` helpers (geometric edge ladder, undersized
    buckets merged into neighbors)."""
    from ..core.bucketing import (
        geometric_edges,
        merge_small_buckets,
        partition_by_size,
    )

    if bucketing == "off" or len(sizes) < 2:
        return None
    parts = merge_small_buckets(
        partition_by_size(sizes, geometric_edges(max(sizes))),
        _BUCKET_MIN_PATTERNS,
    )
    if len(parts) < 2:
        return None
    if bucketing == "auto" and len(sizes) < _BUCKET_AUTO_MIN_P:
        return None
    return parts


def _construct_bucketed(dfas, *, max_states, tile, max_retries, poly_index,
                        distribution, mesh, pattern_axis, fp_backend,
                        expand_backend, bucketing, bucket_growth, weight_fn):
    """The size-bucketed batched driver: partition the bank by DFA state
    count, close each sub-bank with bucket-local ``n_max``/capacity/round
    shapes, and scatter results back to the original pattern order.

    Wall-time attribution stays a *bank-global* rounds-weighted share: the
    merged stats recompute every pattern's ``SFAStats.wall_time_s`` against
    the whole call's wall and the total active-round count across buckets,
    so the attribution contract is bucketing-invariant.
    """
    t0 = time.perf_counter()
    parts = _construction_partition(
        [d.n_states for d in dfas], bucketing
    )
    if parts is None:
        return _construct_batched(
            dfas, max_states=max_states, tile=tile, max_retries=max_retries,
            poly_index=poly_index, distribution=distribution, mesh=mesh,
            pattern_axis=pattern_axis, fp_backend=fp_backend,
            expand_backend=expand_backend, bucket_growth=bucket_growth,
            weight_fn=weight_fn,
        )

    P = len(dfas)
    stats = BankStats(
        method="batched",
        pattern_rounds=np.zeros(P, np.int64),
        retries=np.zeros(P, np.int64),
        pattern_candidates=np.zeros(P, np.int64),
    )
    sfas: list = [None] * P
    blown = np.zeros(P, dtype=bool)
    for edge, idx in parts:
        sub_dfas = [dfas[i] for i in idx]

        def sub_weight_fn(p, attempt, n_words, consts, _idx=idx):
            # The seam keys on *bank-global* pattern position, so forced
            # collisions hit the same pattern bucketed or not. n_words is
            # bucket-local; weight fns must derive weights from it alone.
            return weight_fn(_idx[p], attempt, n_words, consts)

        with obs.span("construct_bank.bucket", edge=int(edge),
                      n_patterns=len(idx),
                      n_max=max(d.n_states for d in sub_dfas)):
            sub = _construct_batched(
                sub_dfas, max_states=max_states, tile=tile,
                max_retries=max_retries, poly_index=poly_index,
                distribution=distribution, mesh=mesh,
                pattern_axis=pattern_axis,
                fp_backend=fp_backend, expand_backend=expand_backend,
                bucket_growth=bucket_growth, weight_fn=sub_weight_fn,
            )
        ii = np.asarray(idx, dtype=np.int64)
        stats.pattern_rounds[ii] = sub.stats.pattern_rounds
        stats.retries[ii] = sub.stats.retries
        stats.pattern_candidates[ii] = sub.stats.pattern_candidates
        stats.rounds += sub.stats.rounds
        blown[ii] = sub.blown
        for j, i in enumerate(idx):
            sfas[i] = sub.sfas[j]
        stats.buckets.append(BucketStats(
            edge=int(edge),
            n_patterns=len(idx),
            n_max=max(d.n_states for d in sub_dfas),
            rounds=sub.stats.rounds,
            blown=int(sub.blown.sum()),
            wall_time_s=sub.stats.wall_time_s,
        ))
    stats.candidates = int(stats.pattern_candidates.sum())
    stats.wall_time_s = time.perf_counter() - t0
    total_rounds = int(stats.pattern_rounds.sum())
    for p in range(P):
        if sfas[p] is not None:
            sfas[p].stats.wall_time_s = (
                stats.wall_time_s * int(stats.pattern_rounds[p]) / total_rounds
                if total_rounds else 0.0
            )
    return BankConstructionResult(sfas=sfas, blown=blown, stats=stats)


def _construct_loop(dfas, *, max_states, max_retries, engine, poly_index=0):
    from .single import construct_sfa

    t0 = time.perf_counter()
    P = len(dfas)
    stats = BankStats(
        method="loop",
        pattern_rounds=np.zeros(P, np.int64),
        retries=np.zeros(P, np.int64),
        pattern_candidates=np.zeros(P, np.int64),
    )
    sfas: list = [None] * P
    blown = np.zeros(P, dtype=bool)
    for p, d in enumerate(dfas):
        try:
            sfa = construct_sfa(
                d, engine=engine, max_states=max_states,
                max_retries=max_retries, poly_index=poly_index,
            )
        except StateBlowup:
            blown[p] = True
            continue
        sfas[p] = sfa
        stats.rounds += sfa.stats.rounds
        stats.pattern_rounds[p] = sfa.stats.rounds
        stats.pattern_candidates[p] = sfa.stats.candidates
    stats.candidates = int(stats.pattern_candidates.sum())
    stats.wall_time_s = time.perf_counter() - t0
    return BankConstructionResult(sfas=sfas, blown=blown, stats=stats)


def _construct_batched(dfas, *, max_states, tile, max_retries, poly_index,
                       distribution, mesh, pattern_axis, fp_backend,
                       expand_backend, bucket_growth, weight_fn):
    t0 = time.perf_counter()
    bank = PatternBank.from_dfas(dfas)  # validates the shared alphabet
    P, n, k = bank.n_patterns, bank.n_max, bank.n_symbols
    if n >= 1 << 16:
        raise ValueError("batched engine packs 16-bit state ids")
    W = (n + 1) // 2

    if distribution == "shard_map":
        if mesh is None:
            mesh = make_mesh((jax.device_count(),), (pattern_axis,))
        quantum = int(np.prod(list(mesh.shape.values())))
    elif distribution == "local":
        quantum = 1
    else:
        raise ValueError(
            f"distribution must be 'local' or 'shard_map', got {distribution!r}"
        )

    # The interpret flag only shapes the pallas stages; pin it for all-xla
    # rounds so the compile-cache key does not split on an irrelevant axis.
    if "pallas" in (fp_backend, expand_backend):
        from ..kernels import ops as kernel_ops

        interpret = kernel_ops._default_interpret()
    else:
        interpret = False

    sched = round_schedule(
        tile=tile, n=n, k=k, max_states=max_states, P=P, quantum=quantum,
        bucket_growth=bucket_growth,
    )
    capacity = sched.capacities[0]

    stats = BankStats(
        method="batched",
        pattern_rounds=np.zeros(P, np.int64),
        retries=np.zeros(P, np.int64),
        pattern_candidates=np.zeros(P, np.int64),
    )

    # -- per-pattern fingerprint constants + initial buffers ------------------
    n_true = bank.n_states.astype(np.int64)
    attempts = np.zeros(P, dtype=np.int64)

    def consts_of(p):
        return BarrettConstants.cached(
            nth_poly_low(poly_index + int(attempts[p]))
        )

    weights_np = np.empty((P, W, 2), dtype=np.uint32)
    limbs_np = np.empty((P, 4), dtype=np.uint32)
    masks_np = np.empty((P, W), dtype=np.uint32)
    fp0_np = np.empty((P, 2), dtype=np.uint32)
    for p in range(P):
        c = consts_of(p)
        weights_np[p] = weight_fn(p, 0, W, c)
        limbs_np[p] = _limbs_of(c)
        masks_np[p] = _word_mask(int(n_true[p]), n)
        fp0_np[p] = _seed_fingerprint(int(n_true[p]), c.poly_low)

    identity = np.arange(n, dtype=np.int32)
    states = jnp.zeros((P, capacity, n), jnp.int32).at[:, 0].set(identity)
    fp_hi = jnp.full((P, capacity), _U32MAX, jnp.uint32).at[:, 0].set(
        jnp.asarray(fp0_np[:, 0])
    )
    fp_lo = jnp.full((P, capacity), _U32MAX, jnp.uint32).at[:, 0].set(
        jnp.asarray(fp0_np[:, 1])
    )
    delta = jnp.zeros((P, capacity, k), jnp.int32)
    n_states = jnp.ones(P, jnp.int32)
    frontier = jnp.zeros(P, jnp.int32)
    weights = jnp.asarray(weights_np)
    limbs = jnp.asarray(limbs_np)
    masks = jnp.asarray(masks_np)
    tables = jnp.asarray(bank.tables)

    n_states_h = np.ones(P, dtype=np.int64)
    frontier_h = np.zeros(P, dtype=np.int64)
    blown = np.zeros(P, dtype=bool)

    # -- the nonblocking host loop -------------------------------------------
    while True:
        runnable = (~blown) & (frontier_h < n_states_h)
        act = np.flatnonzero(runnable)
        if act.size == 0:
            break
        worst = int(n_states_h[act].max()) + tile * k
        if worst > capacity and capacity < sched.capacities[-1]:
            grown = sched.capacity_for(worst)
            pad = grown - capacity
            states = jnp.pad(states, ((0, 0), (0, pad), (0, 0)))
            fp_hi = jnp.pad(fp_hi, ((0, 0), (0, pad)),
                            constant_values=np.uint32(0xFFFFFFFF))
            fp_lo = jnp.pad(fp_lo, ((0, 0), (0, pad)),
                            constant_values=np.uint32(0xFFFFFFFF))
            delta = jnp.pad(delta, ((0, 0), (0, pad), (0, 0)))
            capacity = grown
        bucket = sched.bucket_for(act.size)
        idx_np = np.full(bucket, act[0], dtype=np.int32)
        idx_np[: act.size] = act
        act_np = np.zeros(bucket, dtype=bool)
        act_np[: act.size] = True
        jidx = jnp.asarray(idx_np)
        jact = jnp.asarray(act_np)

        stats.rounds += 1
        stats.pattern_rounds[act] += 1
        stats.pattern_candidates[act] += (
            np.minimum(n_states_h[act] - frontier_h[act], tile) * k
        )

        round_t0 = time.perf_counter()
        with obs.span("construction.round", round=stats.rounds,
                      bucket=bucket, capacity=capacity):
            if distribution == "shard_map":
                round_fn = _sharded_round_exe(
                    mesh, pattern_axis, tile=tile, n=n, k=k,
                    capacity=capacity,
                    fp_backend=fp_backend, expand_backend=expand_backend,
                    interpret=interpret,
                )
                out = round_fn(
                    tables[jidx], states[jidx], fp_hi[jidx], fp_lo[jidx],
                    delta[jidx], n_states[jidx], frontier[jidx],
                    jact, weights[jidx], limbs[jidx], masks[jidx],
                )
                (o_states, o_fp_hi, o_fp_lo, o_delta, o_n, o_frontier,
                 o_coll) = out
                live = jnp.asarray(act)
                states = states.at[live].set(o_states[: act.size])
                fp_hi = fp_hi.at[live].set(o_fp_hi[: act.size])
                fp_lo = fp_lo.at[live].set(o_fp_lo[: act.size])
                delta = delta.at[live].set(o_delta[: act.size])
                n_states = n_states.at[live].set(o_n[: act.size])
                frontier = frontier.at[live].set(o_frontier[: act.size])
                n_states_h[act] = np.asarray(o_n[: act.size], dtype=np.int64)
                frontier_h[act] = np.asarray(
                    o_frontier[: act.size], dtype=np.int64
                )
                coll_np = np.asarray(o_coll[: act.size])
            else:
                step = _local_step_exe(
                    tile=tile, n=n, k=k, capacity=capacity, P=P,
                    bucket=bucket,
                    fp_backend=fp_backend, expand_backend=expand_backend,
                    interpret=interpret,
                )
                states, fp_hi, fp_lo, delta, n_states, frontier, o_coll = \
                    step(
                        tables, states, fp_hi, fp_lo, delta, n_states,
                        frontier, weights, limbs, masks, jidx, jact,
                    )
                n_states_h = np.asarray(n_states).astype(np.int64)
                frontier_h = np.asarray(frontier).astype(np.int64)
                coll_np = np.asarray(o_coll)[: act.size]
        obs.histogram("construction.round_wall_s").observe(
            time.perf_counter() - round_t0
        )

        collided = act[coll_np]
        # Per-pattern polynomial retry, applied as ONE batched scatter per
        # buffer: only collided patterns restart; the others keep progress.
        if collided.size:
            new_w = np.empty((collided.size, W, 2), dtype=np.uint32)
            new_l = np.empty((collided.size, 4), dtype=np.uint32)
            new_fp = np.empty((collided.size, 2), dtype=np.uint32)
            for j, p in enumerate(collided):
                attempts[p] += 1
                stats.retries[p] += 1
                if attempts[p] >= max_retries:
                    raise FingerprintCollision(
                        f"pattern {p}: {max_retries} polynomials all collided"
                    )
                c = consts_of(p)
                new_w[j] = weight_fn(int(p), int(attempts[p]), W, c)
                new_l[j] = _limbs_of(c)
                new_fp[j] = _seed_fingerprint(int(n_true[p]), c.poly_low)
                weights_np[p] = new_w[j]
                limbs_np[p] = new_l[j]
            cidx = jnp.asarray(collided.astype(np.int32))
            weights = weights.at[cidx].set(jnp.asarray(new_w))
            limbs = limbs.at[cidx].set(jnp.asarray(new_l))
            fp_hi = fp_hi.at[cidx, 0].set(jnp.asarray(new_fp[:, 0]))
            fp_lo = fp_lo.at[cidx, 0].set(jnp.asarray(new_fp[:, 1]))
            n_states = n_states.at[cidx].set(jnp.int32(1))
            frontier = frontier.at[cidx].set(jnp.int32(0))
            n_states_h[collided] = 1
            frontier_h[collided] = 0

        blown |= n_states_h > max_states

    # -- crop per-pattern results ---------------------------------------------
    stats.wall_time_s = time.perf_counter() - t0
    stats.candidates = int(stats.pattern_candidates.sum())
    total_rounds = int(stats.pattern_rounds.sum())
    states_np = np.asarray(states)
    delta_np = np.asarray(delta)
    fp_hi_np = np.asarray(fp_hi)
    fp_lo_np = np.asarray(fp_lo)
    sfas: list = [None] * P
    for p in range(P):
        if blown[p]:
            continue
        S = int(n_states_h[p])
        # Rounds-weighted share: the bank's wall belongs to BankStats; a
        # pattern reports only the fraction of rounds it was active in.
        share = (
            stats.wall_time_s * int(stats.pattern_rounds[p]) / total_rounds
            if total_rounds else 0.0
        )
        pstats = SFAStats(
            engine="batched",
            rounds=int(stats.pattern_rounds[p]),
            candidates=int(stats.pattern_candidates[p]),
            wall_time_s=share,
        )
        fps = np.stack([fp_hi_np[p, :S], fp_lo_np[p, :S]], axis=1).astype(
            np.uint32
        )
        sfas[p] = SFA(
            mappings=np.ascontiguousarray(states_np[p, :S, : int(n_true[p])]),
            delta=np.ascontiguousarray(delta_np[p, :S]),
            fingerprints=fps,
            dfa=dfas[p],
            stats=pstats,
        )
    return BankConstructionResult(sfas=sfas, blown=blown, stats=stats)


# --------------------------------------------------------------------------
# The single-pattern jitted engine (P = 1 special case)
# --------------------------------------------------------------------------


def construct_sfa_jax(
    dfa: DFA,
    *,
    poly_index: int = 0,
    max_states: int = 200_000,
    tile: int = 256,
) -> SFA:
    """The jitted TPU-shaped engine — now literally the bank construction
    with one pattern. Raises :class:`FingerprintCollision` on a detected
    collision (the :func:`~repro.construction.construct_sfa` wrapper
    retries with the next polynomial)."""
    result = construct_bank(
        [dfa], max_states=max_states, tile=tile, poly_index=poly_index,
        max_retries=1, method="batched", on_blowup="raise",
    )
    sfa = result.sfas[0]
    sfa.stats.engine = "jax"
    return sfa
