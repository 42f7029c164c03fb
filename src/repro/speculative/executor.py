"""The jitted speculative executor: m-wide chunk passes + validate/repair.

Enumeration resolves a chunk's transition function for *all* ``n`` states —
``O(L·n)`` gathers per pattern — because it cannot know the chunk's entry
state before its predecessor finishes. Speculation breaks the chain the
other way (1210.5093 / PaREM 1412.1741): run every chunk from ``m`` *likely*
entry states in one batched pass (a stacked ``(m, chunks)`` state axis —
the same shape trick as enumeration, just ``m`` lanes instead of ``n``),
then walk the chunks once, cheaply, to check each chunk's true entry state
(its predecessor's exact exit) against the speculated set:

* **hit** — the entry was speculated; adopt that lane's precomputed exit.
  The adopted exit is exactly what a sequential run would produce, so
  correctness propagates chunk to chunk by induction.
* **miss** — re-scan *only* the first missed chunk of each broken
  (pattern, doc) lane from its now-known entry (a fixed-shape
  ``(P, D, chunk_len)`` repair pass — one chunk per lane per round), and
  re-validate. Each round resolves at least one more chunk per unresolved
  lane, so ``max_rounds`` bounds the loop; anything still unresolved is
  reported for the caller's guaranteed enumeration fallback.

Everything is fixed-shape: the validation walk is a ``lax.scan`` over the
chunk axis on ``(P, D)`` lanes, and the repair loop is a ``lax.while_loop``
whose body re-runs the same two fixed-shape stages — one compiled program
per (bank shape, corpus shape), no recompiles across rounds.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..compat import shard_map as compat_shard_map


@dataclass(frozen=True)
class SpeculationStats:
    """What one speculative scan actually did.

    ``total_chunks`` counts every (pattern, doc, chunk) cell the executor
    resolved; ``hit_chunks`` of those were settled by speculation alone and
    ``repaired_chunks`` by targeted re-scans (on a fully resolved scan,
    ``hit_chunks + repaired_chunks == total_chunks``). ``repair_rounds`` is
    the deepest validate/repair iteration count any executor invocation
    needed (0 when every chunk's entry was speculated), and
    ``fallback_lanes`` counts (pattern, doc) lanes the round bound left for
    the enumeration fallback — still bit-identical, just not cheap.
    """

    total_chunks: int = 0
    hit_chunks: int = 0
    repaired_chunks: int = 0
    repair_rounds: int = 0
    fallback_lanes: int = 0

    @property
    def hit_rate(self) -> float:
        """Fraction of chunks settled by speculation alone (1.0 when empty)."""
        if not self.total_chunks:
            return 1.0
        return self.hit_chunks / self.total_chunks

    def merged(self, other: "SpeculationStats") -> "SpeculationStats":
        """Combine stats across pattern groups / length batches of one scan."""
        return replace(
            self,
            total_chunks=self.total_chunks + other.total_chunks,
            hit_chunks=self.hit_chunks + other.hit_chunks,
            repaired_chunks=self.repaired_chunks + other.repaired_chunks,
            repair_rounds=max(self.repair_rounds, other.repair_rounds),
            fallback_lanes=self.fallback_lanes + other.fallback_lanes,
        )


# --------------------------------------------------------------------------
# The core (traced once; shared by the local jit and the shard_map body)
# --------------------------------------------------------------------------


def _run_chunk_states(table, states, chunk):
    """Advance a vector of states through one chunk: (n, k), (m,), (Lc,) -> (m,)."""
    def step(sv, sym):
        return table[sv, sym], None

    out, _ = jax.lax.scan(step, states, chunk)
    return out


def _speculative_core(tables, spec_states, starts, corpus,
                      n_chunks: int, max_rounds: int):
    """-> (finals (P, D) int32, resolved (P, D) bool, hit_chunks, repaired,
    rounds) — finals are exact wherever ``resolved``; unresolved lanes keep
    their last verified state and MUST be recomputed by the caller."""
    Pn = tables.shape[0]
    D, L = corpus.shape
    C = n_chunks
    Lc = L // C
    chunks = corpus.reshape(D, C, Lc)
    starts = jnp.broadcast_to(starts.astype(jnp.int32).reshape(Pn, -1),
                              (Pn, D))

    # Stage 1 — the one batched speculative pass: every (pattern, doc, chunk)
    # cell runs from all m speculated states at once. O(L·m) per pattern,
    # the whole reason this beats the O(L·n) enumeration gathers.
    exits = jax.vmap(
        lambda t, sp: jax.vmap(
            jax.vmap(lambda ch: _run_chunk_states(t, sp, ch))
        )(chunks)
    )(tables, spec_states)                               # (P, D, C, m)

    c_idx = jnp.arange(C, dtype=jnp.int32)

    def validate(rep_exit, rep_mask):
        """Walk the chunk axis once, threading exact entry states.

        ``rep_exit``/``rep_mask`` (P, D, C) carry repaired chunks from
        earlier rounds — a repaired chunk's exit overrides speculation.
        Returns (finals, resolved, miss_c, miss_entry, hit_chunks) where
        ``miss_c``/``miss_entry`` locate the first unrepaired miss of each
        still-broken lane (the next round's repair target).
        """
        def step(carry, xs):
            cur, alive, miss_c, miss_entry, hits = carry
            ex_c, rep_e, rep_m, c = xs
            match = spec_states[:, None, :] == cur[:, :, None]   # (P, D, m)
            hit = jnp.any(match, axis=-1)
            lane = jnp.argmax(match, axis=-1)
            spec_exit = jnp.take_along_axis(
                ex_c, lane[..., None], axis=-1
            )[..., 0]
            ok = rep_m | hit
            nxt = jnp.where(rep_m, rep_e, spec_exit)
            newly_missed = alive & ~ok
            hits = hits + jnp.sum(alive & ~rep_m & hit, dtype=jnp.int32)
            miss_c = jnp.where(newly_missed, c, miss_c)
            miss_entry = jnp.where(newly_missed, cur, miss_entry)
            cur = jnp.where(alive & ok, nxt, cur)
            alive = alive & ok
            return (cur, alive, miss_c, miss_entry, hits), None

        init = (
            starts,
            jnp.ones((Pn, D), dtype=bool),
            jnp.full((Pn, D), C, dtype=jnp.int32),
            jnp.zeros((Pn, D), dtype=jnp.int32),
            jnp.zeros((), dtype=jnp.int32),
        )
        xs = (
            jnp.moveaxis(exits, 2, 0),          # (C, P, D, m)
            jnp.moveaxis(rep_exit, 2, 0),       # (C, P, D)
            jnp.moveaxis(rep_mask, 2, 0),
            c_idx,
        )
        carry, _ = jax.lax.scan(step, init, xs)
        return carry

    d_idx = jnp.arange(D)

    def repair(rep_exit, rep_mask, alive, miss_c, miss_entry):
        """Re-scan the first missed chunk of every broken lane from its
        exact entry — one (P, D, Lc) fixed-shape pass per round."""
        c = jnp.minimum(miss_c, C - 1)                   # (P, D); clip is inert
        lane_chunks = chunks[d_idx[None, :], c]          # (P, D, Lc)

        def run_lane(t, ch, e):
            def step(s, sym):
                return t[s, sym], None

            out, _ = jax.lax.scan(step, e, ch)
            return out

        exact = jax.vmap(
            lambda t, chs, es: jax.vmap(
                lambda ch, e: run_lane(t, ch, e)
            )(chs, es)
        )(tables, lane_chunks, miss_entry)               # (P, D)
        sel = (c_idx[None, None, :] == c[:, :, None]) & (~alive)[:, :, None]
        rep_exit = jnp.where(sel, exact[:, :, None], rep_exit)
        rep_mask = rep_mask | sel
        return rep_exit, rep_mask

    def cond(state):
        _, _, rounds, alive, _, _, _, _ = state
        return (~jnp.all(alive)) & (rounds < max_rounds)

    def body(state):
        rep_exit, rep_mask, rounds, alive, miss_c, miss_entry, _, _ = state
        rep_exit, rep_mask = repair(rep_exit, rep_mask, alive, miss_c, miss_entry)
        cur, alive, miss_c, miss_entry, hits = validate(rep_exit, rep_mask)
        return (rep_exit, rep_mask, rounds + 1, alive, miss_c, miss_entry,
                cur, hits)

    rep_exit = jnp.zeros((Pn, D, C), dtype=jnp.int32)
    rep_mask = jnp.zeros((Pn, D, C), dtype=bool)
    cur, alive, miss_c, miss_entry, hits = validate(rep_exit, rep_mask)
    state = (rep_exit, rep_mask, jnp.zeros((), dtype=jnp.int32),
             alive, miss_c, miss_entry, cur, hits)
    state = jax.lax.while_loop(cond, body, state)
    rep_exit, rep_mask, rounds, alive, miss_c, miss_entry, cur, hits = state
    repaired = jnp.sum(rep_mask, dtype=jnp.int32)
    return cur, alive, hits, repaired, rounds


@functools.partial(jax.jit, static_argnames=("n_chunks", "max_rounds"))
def speculative_bank_finals(tables: jnp.ndarray, spec_states: jnp.ndarray,
                            starts: jnp.ndarray, corpus: jnp.ndarray,
                            n_chunks: int = 8, max_rounds: int = 8):
    """Speculative final states of every (pattern, doc).

    ``tables`` (P, n, k) padded enumeration tables; ``spec_states`` (P, m)
    speculated boundary states (a hot-state profile stack); ``starts`` (P,)
    per pattern or (P, D) per lane; ``corpus`` (D, L) with ``L`` divisible
    by ``n_chunks``.

    -> ``(finals (P, D) int32, resolved (P, D) bool, hit_chunks, repaired,
    rounds)``. ``finals[p, d]`` is **exact** wherever ``resolved[p, d]`` —
    every adopted chunk exit was validated against the true entry state —
    and callers must recompute unresolved lanes (the enumeration fallback
    in ``Scanner``). The speculation quality only moves work between the
    hit/repaired/fallback buckets, never the result.
    """
    return _speculative_core(tables, spec_states, starts, corpus,
                             n_chunks, max_rounds)


# --------------------------------------------------------------------------
# shard_map distribution (docs over the data axis, like the mapping path)
# --------------------------------------------------------------------------


def distributed_speculative_finals_fn(mesh: Mesh, data_axis: str = "data",
                                      n_chunks: int = 8, max_rounds: int = 8):
    """Scanner's shard_map path for speculative mode: docs shard over
    ``data_axis`` (tables/profiles replicated), each device runs the full
    local validate/repair loop on its shard — trip counts may differ per
    device; there are no collectives inside the loop, so that is fine —
    then finals/resolved gather on the doc axis and the counters combine
    (psum for chunk counts, pmax for the round depth). Returns a jitted
    ``fn(tables, spec_states, starts, corpus)`` with the local output
    contract of :func:`speculative_bank_finals`.
    """

    def local(tables, spec_states, starts, corpus_shard):
        finals, resolved, hits, repaired, rounds = _speculative_core(
            tables, spec_states, starts, corpus_shard, n_chunks, max_rounds
        )
        finals = jax.lax.all_gather(finals, data_axis, axis=1, tiled=True)
        resolved = jax.lax.all_gather(resolved, data_axis, axis=1, tiled=True)
        hits = jax.lax.psum(hits, data_axis)
        repaired = jax.lax.psum(repaired, data_axis)
        rounds = jax.lax.pmax(rounds, data_axis)
        return finals, resolved, hits, repaired, rounds

    @jax.jit
    def fn(tables, spec_states, starts, corpus):
        return compat_shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P(), P(data_axis)),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False,
        )(tables, spec_states, starts, corpus)

    return fn
