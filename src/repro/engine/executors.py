"""Jitted execution primitives behind the :class:`~repro.engine.Scanner`.

This module is the single home of every parallel matching routine: the
single-pattern chunk matchers, the banked (multi-automaton) matchers in both
enumeration and stacked-SFA form, the Pallas inner-loop variants, and the
``shard_map`` distributed builders. They were moved here from
``core/matching.py`` / ``core/multipattern.py`` in the engine redesign — the
old names survive there as thin deprecated shims that delegate to this
module, so nothing downstream breaks while the :class:`Scanner` facade
becomes the public contract.

Layout conventions (shared with ``core.multipattern.PatternBank``):

* enumeration tables are ``(P, n, k)`` int32, padded rows are self-loops;
* stacked SFA tables are ``deltas (P, S, k)`` + ``sfa_maps (P, S, n)`` —
  per-pattern SFA transition tables and state->mapping lookup stacks padded
  the same way (delta padding rows self-loop, mapping padding is identity),
  so the SFA path's chunk functions are *bit-identical* to enumeration's on
  the padded layout;
* chunk functions combine with ``monoid.function_monoid`` everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..compat import shard_map as compat_shard_map
from ..construction import SFA
from ..core import monoid as M
from ..core.dfa import DFA
from ..core.matching import (
    chunk_accept_trace,
    chunk_mapping_enumeration,
    chunk_state_sfa,
)

FN = M.function_monoid()


# --------------------------------------------------------------------------
# Single-pattern parallel matching (ex core/matching.py)
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_chunks",))
def match_parallel_enumeration(table: jnp.ndarray, symbols: jnp.ndarray,
                               n_chunks: int = 8) -> jnp.ndarray:
    """Parallel match via enumeration; returns the mapping of the whole input.

    The input length must be divisible by ``n_chunks`` (callers pad; padding
    symbols would corrupt the composed function otherwise).
    """
    L = symbols.shape[0]
    assert L % n_chunks == 0, "pad input to a multiple of n_chunks"
    chunks = symbols.reshape(n_chunks, L // n_chunks)
    mappings = jax.vmap(lambda c: chunk_mapping_enumeration(table, c))(chunks)
    return M.reduce(FN, mappings, axis=0)


@functools.partial(jax.jit, static_argnames=("n_chunks",))
def match_parallel_sfa(delta_s: jnp.ndarray, sfa_mappings: jnp.ndarray,
                       symbols: jnp.ndarray, n_chunks: int = 8) -> jnp.ndarray:
    """Parallel match via the SFA (paper's method); returns the input mapping."""
    L = symbols.shape[0]
    assert L % n_chunks == 0
    chunks = symbols.reshape(n_chunks, L // n_chunks)
    final_states = jax.vmap(lambda c: chunk_state_sfa(delta_s, c))(chunks)
    mappings = sfa_mappings[final_states]  # (n_chunks, n)
    return M.reduce(FN, mappings, axis=0)


@functools.partial(jax.jit, static_argnames=("n_chunks",))
def find_matches_parallel(table: jnp.ndarray, accepting: jnp.ndarray,
                          symbols: jnp.ndarray, start: int,
                          n_chunks: int = 8) -> jnp.ndarray:
    """Per-position accept flags, computed in two parallel passes:
    (1) chunk functions + exclusive scan -> entry state per chunk;
    (2) per-chunk accept traces from the entry states."""
    L = symbols.shape[0]
    assert L % n_chunks == 0
    chunks = symbols.reshape(n_chunks, L // n_chunks)
    mappings = jax.vmap(lambda c: chunk_mapping_enumeration(table, c))(chunks)
    prefix = M.exclusive_scan(FN, mappings, axis=0)      # (n_chunks, n)
    entry = prefix[:, start]                              # (n_chunks,)
    flags = jax.vmap(lambda c, e: chunk_accept_trace(table, accepting, c, e))(
        chunks, entry
    )
    return flags.reshape(L)


def accepts_parallel(dfa: DFA, text: str, n_chunks: int = 8,
                     sfa: SFA | None = None) -> bool:
    """End-to-end helper: does ``text`` match? (pads to chunk multiple)."""
    symbols = jnp.asarray(dfa.encode(text))
    L = symbols.shape[0]
    if L % n_chunks:
        # The unpadded tail is processed sequentially — cheap (< chunk_len).
        head_len = L - (L % n_chunks)
        head = symbols[:head_len]
        tail = symbols[head_len:]
    else:
        head, tail = symbols, symbols[:0]
    if head.shape[0]:
        if sfa is not None:
            mapping = match_parallel_sfa(
                jnp.asarray(sfa.delta), jnp.asarray(sfa.mappings), head, n_chunks
            )
        else:
            mapping = match_parallel_enumeration(jnp.asarray(dfa.table), head, n_chunks)
        state = int(mapping[dfa.start])
    else:
        state = dfa.start
    state = dfa.run(np.asarray(tail), state=state)
    return bool(dfa.accepting[state])


def distributed_match_fn(mesh: Mesh, table_shape: tuple, axis_name: str = "data"):
    """Build a pjit-able distributed matcher for a given mesh.

    Input ``symbols`` (L,) is sharded over ``axis_name``; each device runs
    enumeration matching on its shard (vectorized over sub-chunks for VPU
    utilization), then per-device functions combine via ``shard_reduce``
    (one all_gather of n-int vectors — the paper's result reduction).
    Returns ``mapping`` (n,) replicated.
    """

    def local_match(table, sym_shard, sub_chunks: int):
        L = sym_shard.shape[0]
        chunks = sym_shard.reshape(sub_chunks, L // sub_chunks)
        mappings = jax.vmap(lambda c: chunk_mapping_enumeration(table, c))(chunks)
        local = M.reduce(FN, mappings, axis=0)
        return M.shard_reduce(FN, local[None], axis_name)[0]

    @functools.partial(jax.jit, static_argnames=("sub_chunks",))
    def matcher(table, symbols, sub_chunks: int = 8):
        fn = compat_shard_map(
            functools.partial(local_match, sub_chunks=sub_chunks),
            mesh=mesh,
            in_specs=(P(), P(axis_name)),
            out_specs=P(),
            check_vma=False,
        )
        return fn(table, symbols)

    return matcher


def throughput_matcher(mesh: Mesh, start: int = 0, axis_name: str = "data"):
    """Batched many-strings matcher: (B, L) inputs sharded over ``axis_name``
    on the batch axis, each row matched independently (the network-security
    style throughput workload from the related work, for completeness)."""

    def local(table, accepting, batch):
        def per_row(row):
            mapping = chunk_mapping_enumeration(table, row)
            return accepting[mapping[start]]

        return jax.vmap(per_row)(batch)

    @jax.jit
    def matcher(table, accepting, batch):
        fn = compat_shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(), P(axis_name)),
            out_specs=P(axis_name),
            check_vma=False,
        )
        return fn(table, accepting, batch)

    return matcher


# --------------------------------------------------------------------------
# Sequential composition (NumPy; ragged tails, reference backend, streams)
# --------------------------------------------------------------------------


def compose_sequential(tables: np.ndarray, mapping: np.ndarray,
                       syms: np.ndarray) -> np.ndarray:
    """Extend per-pattern transition functions by ``syms``, one symbol at a
    time: ``m'[p, q] = tables[p, m[p, q], sym]``. (Pg, n, k), (Pg, n), (L,)
    -> (Pg, n). The exact NumPy twin of the chunk matchers — every ragged
    tail, stream remainder, and reference-backend path funnels through here
    so the bit-identity contract has a single sequential implementation.
    """
    rows = np.arange(tables.shape[0])[:, None]
    m = mapping
    for sym in np.asarray(syms):
        m = tables[rows, m, int(sym)]
    return m


def advance_states_sequential(tables: np.ndarray, states: np.ndarray,
                              tail: np.ndarray) -> np.ndarray:
    """Advance per-(pattern, doc) *states* through per-doc tail symbols:
    ``s'[p, d] = tables[p, s[p, d], tail[d, t]]`` folded over ``t``.
    (Pg, n, k), (Pg, D), (D, T) -> (Pg, D). The state-vector twin of
    :func:`compose_sequential` for the speculative path, whose head
    executor produces final *states* rather than whole mappings — ragged
    tails advance here, one vectorized gather per tail symbol.
    """
    rows = np.arange(tables.shape[0])[:, None]
    tail = np.asarray(tail)
    s = np.asarray(states, dtype=np.int64)
    for t in range(tail.shape[1]):
        s = tables[rows, s, tail[None, :, t]]
    return s.astype(np.int32)


# --------------------------------------------------------------------------
# Banked matchers, enumeration mode (ex core/multipattern.py)
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_chunks",))
def match_bank_parallel(tables: jnp.ndarray, symbols: jnp.ndarray,
                        n_chunks: int = 8) -> jnp.ndarray:
    """Final mappings of one input under every pattern: (P, n, k), (L,) -> (P, n)."""
    L = symbols.shape[0]
    assert L % n_chunks == 0, "pad input to a multiple of n_chunks"
    chunks = symbols.reshape(n_chunks, L // n_chunks)
    mappings = jax.vmap(
        lambda t: jax.vmap(lambda c: chunk_mapping_enumeration(t, c))(chunks)
    )(tables)                                  # (P, n_chunks, n)
    return M.reduce(FN, mappings, axis=1)      # (P, n)


def _bank_doc_mappings(tables, corpus, n_chunks):
    """Enumeration final mapping of every (pattern, doc): -> (P, D, n).

    All (pattern, doc, chunk) cells compute in one doubly-vmapped batch over
    the flattened ``(D * n_chunks)`` chunk axis; composition is one monoid
    reduce over the chunk axis, batched over patterns x docs.
    """
    D, L = corpus.shape
    chunks = corpus.reshape(D * n_chunks, L // n_chunks)
    fns = jax.vmap(
        lambda t: jax.vmap(lambda c: chunk_mapping_enumeration(t, c))(chunks)
    )(tables)                                  # (P, D * n_chunks, n)
    Pn, _, n = fns.shape
    return M.reduce(FN, fns.reshape(Pn, D, n_chunks, n), axis=2)


@functools.partial(jax.jit, static_argnames=("n_chunks",))
def bank_doc_mappings(tables: jnp.ndarray, corpus: jnp.ndarray,
                      n_chunks: int = 8) -> jnp.ndarray:
    return _bank_doc_mappings(tables, corpus, n_chunks)


def _hits_of_mappings(maps, accepting, starts):
    """(P, D, n) final mappings -> (P, D) accept flags."""

    def per_pattern(m, acc, start):
        return acc[m[:, start]]

    return jax.vmap(per_pattern)(maps, accepting, starts)


def _bank_hits(tables, accepting, starts, corpus, n_chunks):
    maps = _bank_doc_mappings(tables, corpus, n_chunks)
    return _hits_of_mappings(maps, accepting, starts)


@functools.partial(jax.jit, static_argnames=("n_chunks",))
def bank_hits(tables: jnp.ndarray, accepting: jnp.ndarray, starts: jnp.ndarray,
              corpus: jnp.ndarray, n_chunks: int = 8) -> jnp.ndarray:
    """Hit matrix of a corpus against the bank: (D, L) int32 -> (P, D) bool."""
    return _bank_hits(tables, accepting, starts, corpus, n_chunks)


@functools.partial(jax.jit, static_argnames=("n_chunks",))
def census_bank(tables: jnp.ndarray, accepting: jnp.ndarray, starts: jnp.ndarray,
                corpus: jnp.ndarray, n_chunks: int = 8) -> jnp.ndarray:
    """Per-pattern hit counts over a corpus: (P,) int32 — the ScanProsite
    census (how many database sequences carry each signature)."""
    hits = _bank_hits(tables, accepting, starts, corpus, n_chunks)
    return jnp.sum(hits, axis=1, dtype=jnp.int32)


# --------------------------------------------------------------------------
# Banked matchers, stacked-SFA mode (the paper's single-lookup inner loop,
# lifted to the bank axis — ROADMAP "SFA-mode bank matching")
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_chunks",))
def match_bank_parallel_sfa(deltas: jnp.ndarray, sfa_maps: jnp.ndarray,
                            symbols: jnp.ndarray, n_chunks: int = 8
                            ) -> jnp.ndarray:
    """SFA-mode bank matching: (P, S, k) deltas + (P, S, n) mapping stacks.

    Each chunk runs every pattern's SFA like a DFA (one lookup per char) from
    SFA state 0 (identity), then the chunk's transition function is read off
    the final SFA state — the paper's method, vmapped over the pattern axis.
    Returns (P, n), bit-identical to :func:`match_bank_parallel` on the same
    padded layout.
    """
    L = symbols.shape[0]
    assert L % n_chunks == 0, "pad input to a multiple of n_chunks"
    chunks = symbols.reshape(n_chunks, L // n_chunks)
    finals = jax.vmap(
        lambda d: jax.vmap(lambda c: chunk_state_sfa(d, c))(chunks)
    )(deltas)                                    # (P, n_chunks)
    mappings = jax.vmap(lambda m, f: m[f])(sfa_maps, finals)  # (P, n_chunks, n)
    return M.reduce(FN, mappings, axis=1)


def _bank_doc_mappings_sfa(deltas, sfa_maps, corpus, n_chunks):
    D, L = corpus.shape
    chunks = corpus.reshape(D * n_chunks, L // n_chunks)
    finals = jax.vmap(
        lambda d: jax.vmap(lambda c: chunk_state_sfa(d, c))(chunks)
    )(deltas)                                    # (P, D * n_chunks)
    mapped = jax.vmap(lambda m, f: m[f])(sfa_maps, finals)  # (P, D*n_chunks, n)
    Pn, _, n = mapped.shape
    return M.reduce(FN, mapped.reshape(Pn, D, n_chunks, n), axis=2)


@functools.partial(jax.jit, static_argnames=("n_chunks",))
def bank_doc_mappings_sfa(deltas: jnp.ndarray, sfa_maps: jnp.ndarray,
                          corpus: jnp.ndarray, n_chunks: int = 8) -> jnp.ndarray:
    """SFA-mode final mapping of every (pattern, doc): -> (P, D, n)."""
    return _bank_doc_mappings_sfa(deltas, sfa_maps, corpus, n_chunks)


# --------------------------------------------------------------------------
# Pallas inner-loop variants (match_bank_chunks_pallas wired in — ROADMAP)
# --------------------------------------------------------------------------


def bank_doc_mappings_pallas(tables: jnp.ndarray, corpus: jnp.ndarray,
                             n_chunks: int = 8, *, block_b: int = 128,
                             interpret: bool | None = None) -> jnp.ndarray:
    """Enumeration doc mappings with the Pallas multi-automaton kernel as the
    chunk-function inner loop: (P, n, k), (D, L) -> (P, D, n). The kernel's
    grid iterates (pattern, chunk-block) with the VMEM-resident transposed
    table swapped once per pattern."""
    from ..kernels import ops

    D, L = corpus.shape
    chunks = corpus.reshape(D * n_chunks, L // n_chunks)
    fns = ops.match_bank_chunks(tables, chunks, block_b=block_b,
                                interpret=interpret)   # (P, D*n_chunks, n)
    Pn, _, n = fns.shape
    return M.reduce(FN, fns.reshape(Pn, D, n_chunks, n), axis=2)


def bank_doc_mappings_sfa_pallas(deltas: jnp.ndarray, sfa_maps: jnp.ndarray,
                                 corpus: jnp.ndarray, n_chunks: int = 8, *,
                                 block_b: int = 128,
                                 interpret: bool | None = None) -> jnp.ndarray:
    """SFA-mode doc mappings through the same Pallas kernel: the SFA delta
    *is* a DFA table, so the kernel runs each chunk from SFA state 0 (the
    identity start) alone to its final SFA state, and the mapping stack
    turns that into the DFA-state function."""
    from ..kernels import ops

    D, L = corpus.shape
    chunks = corpus.reshape(D * n_chunks, L // n_chunks)
    fns = ops.match_bank_chunks(deltas, chunks, starts=1, block_b=block_b,
                                interpret=interpret)   # (P, D*n_chunks, 1)
    finals = fns[..., 0]                               # (P, D*n_chunks)
    mapped = jax.vmap(lambda m, f: m[f])(sfa_maps, finals)
    Pn, _, n = mapped.shape
    return M.reduce(FN, mapped.reshape(Pn, D, n_chunks, n), axis=2)


# --------------------------------------------------------------------------
# Distributed builders (shard_map over the mesh)
# --------------------------------------------------------------------------


def distributed_bank_matcher(mesh: Mesh, pattern_axis: str = "model",
                             data_axis: str = "data"):
    """Build a jitted matcher distributing patterns x chunks over ``mesh``.

    ``tables`` (P, n, k) shards over ``pattern_axis``; ``symbols`` (L,)
    shards over ``data_axis``. Each device computes the chunk functions of
    its pattern shard on its data shard, then a single fused monoid
    reduction — ``shard_reduce`` batched over the local pattern axis, i.e.
    ONE all_gather of (P_local, n) int vectors along ``data_axis`` — yields
    the whole-input mapping per pattern. Output: (P, n), P-sharded over
    ``pattern_axis`` and replicated along ``data_axis``.
    """

    def local_match(tables, sym_shard, sub_chunks: int):
        Lc = sym_shard.shape[0]
        chunks = sym_shard.reshape(sub_chunks, Lc // sub_chunks)
        mappings = jax.vmap(
            lambda t: jax.vmap(lambda c: chunk_mapping_enumeration(t, c))(chunks)
        )(tables)                                    # (P_local, sub_chunks, n)
        local = M.reduce(FN, mappings, axis=1)       # (P_local, n)
        return M.shard_reduce(FN, local, data_axis)  # fused over data axis

    @functools.partial(jax.jit, static_argnames=("sub_chunks",))
    def matcher(tables, symbols, sub_chunks: int = 8):
        fn = compat_shard_map(
            functools.partial(local_match, sub_chunks=sub_chunks),
            mesh=mesh,
            in_specs=(P(pattern_axis), P(data_axis)),
            out_specs=P(pattern_axis),
            check_vma=False,
        )
        return fn(tables, symbols)

    return matcher


def distributed_census_fn(mesh: Mesh, pattern_axis: str = "model",
                          data_axis: str = "data", n_chunks: int = 8):
    """Distributed census: corpus rows shard over ``data_axis``, patterns
    over ``pattern_axis``; per-device partial counts combine with one psum."""

    def local(tables, accepting, starts, corpus_shard):
        hits = _bank_hits(tables, accepting, starts, corpus_shard, n_chunks)
        counts = jnp.sum(hits, axis=1, dtype=jnp.int32)
        return jax.lax.psum(counts, data_axis)

    @jax.jit
    def census(tables, accepting, starts, corpus):
        fn = compat_shard_map(
            local,
            mesh=mesh,
            in_specs=(P(pattern_axis), P(pattern_axis), P(pattern_axis),
                      P(data_axis)),
            out_specs=P(pattern_axis),
            check_vma=False,
        )
        return fn(tables, accepting, starts, corpus)

    return census


def distributed_doc_mappings_fn(mesh: Mesh, data_axis: str = "data",
                                n_chunks: int = 8, sfa_mode: bool = False):
    """Scanner's shard_map path: docs shard over ``data_axis`` (patterns
    replicated — bank stacks are small next to corpora), each device computes
    its doc shard's final mappings locally, and the doc axis is gathered back.
    Returns a jitted ``fn(arrays..., corpus) -> (P, D, n)`` replicated.
    """

    if sfa_mode:
        def local(deltas, sfa_maps, corpus_shard):
            maps = _bank_doc_mappings_sfa(deltas, sfa_maps, corpus_shard, n_chunks)
            return jax.lax.all_gather(maps, data_axis, axis=1, tiled=True)

        @jax.jit
        def fn(deltas, sfa_maps, corpus):
            return compat_shard_map(
                local, mesh=mesh,
                in_specs=(P(), P(), P(data_axis)),
                out_specs=P(),
                check_vma=False,
            )(deltas, sfa_maps, corpus)

        return fn

    def local(tables, corpus_shard):
        maps = _bank_doc_mappings(tables, corpus_shard, n_chunks)
        return jax.lax.all_gather(maps, data_axis, axis=1, tiled=True)

    @jax.jit
    def fn(tables, corpus):
        return compat_shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(data_axis)),
            out_specs=P(),
            check_vma=False,
        )(tables, corpus)

    return fn


# --------------------------------------------------------------------------
# Prefix-scan census: sliding windows without recomputing shared blocks
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("m",))
def sliding_window_mappings(block_maps: jnp.ndarray, m: int) -> jnp.ndarray:
    """All length-``m`` sliding-window compositions of consecutive block
    transition functions — ``(Pg, B, n) -> (Pg, B - m + 1, n)`` where output
    ``w`` is ``block w ∘then∘ ... ∘then∘ block w+m-1``.

    ``Scanner.census`` on materialized windows recomputes every block's
    function ``m`` times; this is the Gil–Werman trick on the function
    monoid instead: tile the block axis into groups of ``m``, run one
    *suffix* :func:`repro.core.monoid.scan` and one *prefix* scan per tile
    (each block's function enters exactly two log-depth scans), and stitch
    window ``w = t·m + j`` as ``suffix[t, j] ∘then∘ prefix[t+1, j-1]``
    (identity when ``j = 0``). Function composition is exactly associative
    on int32 gathers, so results are bit-identical to the naive per-window
    composition no matter how the tiling falls.
    """
    Pg, B, n = block_maps.shape
    W = B - m + 1
    assert W >= 1, "need at least m blocks"
    if m == 1:
        return block_maps
    T = -(-B // m)  # tiles of m blocks, last one padded with identities
    ident = jnp.broadcast_to(jnp.arange(n, dtype=block_maps.dtype), (Pg, 1, n))
    pad = jnp.broadcast_to(ident, (Pg, T * m - B, n))
    x = jnp.concatenate([block_maps, pad], axis=1).reshape(Pg, T, m, n)
    # A reverse scan folds the right end in first, so the suffix combine
    # "block j then j+1 then ..." needs the argument-flipped monoid.
    flipped = M.Monoid(lambda a, b: FN.combine(b, a), FN.identity, FN.name)
    suffix = M.scan(flipped, x, axis=2, reverse=True)  # [t,j] = tm+j..tm+m-1
    prefix = M.scan(FN, x, axis=2)                     # [t,j] = tm..tm+j
    # prefix, shifted one block right within each tile (j=0 -> identity) and
    # one whole tile down: flat index w + m lands on tile t+1, offset j.
    shifted = jnp.concatenate(
        [jnp.broadcast_to(ident[:, None], (Pg, T, 1, n)), prefix[:, :, :-1]],
        axis=2,
    )
    extra = jnp.broadcast_to(ident[:, None], (Pg, 1, m, n))
    shifted = jnp.concatenate([shifted, extra], axis=1)    # (Pg, T+1, m, n)
    s_flat = suffix.reshape(Pg, T * m, n)[:, :W]
    q_flat = shifted.reshape(Pg, (T + 1) * m, n)[:, m:m + W]
    return FN.combine(s_flat, q_flat)
