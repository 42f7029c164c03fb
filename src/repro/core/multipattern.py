"""Multi-pattern SFA matching: batched PROSITE scans (paper §IV, one level up).

The paper's evaluation workload is the PROSITE protein database — *hundreds*
of signatures scanned over the same corpus. ``core.matching`` parallelizes a
single DFA over input chunks (the fine-grained axis); this module adds the
coarse-grained axis the paper's §IV task parallelism exploits: run **P
automata at once** by stacking their transition tables into one padded
``(P, n_max, k)`` array and vmapping the chunk matchers over the pattern
axis as well as the chunk axis.

Padding story
-------------
Patterns compile to DFAs of very different sizes, so tables are padded to
the bank's ``n_max`` with **self-loop rows** (state ``j >= n_i`` maps every
symbol back to ``j``). Self-loops keep every table entry a valid state id
(gathers never go out of range under vmap) and make the padded states inert:
they are unreachable from real states, and under function composition a
padded entry ``f[q] = q`` stays the identity. Per-pattern true sizes ride
along in ``PatternBank.n_states`` so results can be cropped when needed.

The batched, distributed, and Pallas matchers that used to live here moved
to ``repro.engine.executors`` behind the :class:`repro.engine.Scanner`
facade (which also adds the stacked-SFA bank mode this module's enumeration
matchers lacked). This module keeps the data structures — ``PatternBank``,
``bucket_by_size``, and the ``census_sequential`` oracle. (The deprecation
shims that bridged the move were removed after two further PRs touched every
call site, per the PR-2 policy.)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Mapping, Sequence

import jax.numpy as jnp
import numpy as np

from .dfa import DFA
from .regex import AMINO_ACIDS


# --------------------------------------------------------------------------
# The bank: P automata as one padded table stack
# --------------------------------------------------------------------------


@dataclass
class PatternBank:
    """``P`` complete DFAs over one alphabet, padded to a common state count.

    ``tables[p]`` is pattern ``p``'s transition table, rows ``>= n_states[p]``
    are self-loops; ``accepting[p]``/``starts[p]`` follow the same layout.
    """

    tables: np.ndarray     # (P, n_max, k) int32
    accepting: np.ndarray  # (P, n_max) bool
    starts: np.ndarray     # (P,) int32
    n_states: np.ndarray   # (P,) int32 — true (unpadded) state counts
    ids: tuple
    alphabet: str

    @property
    def n_patterns(self) -> int:
        return int(self.tables.shape[0])

    @property
    def n_max(self) -> int:
        return int(self.tables.shape[1])

    @property
    def n_symbols(self) -> int:
        return int(self.tables.shape[2])

    def encode(self, text: str) -> np.ndarray:
        sym = {c: i for i, c in enumerate(self.alphabet)}
        return np.asarray([sym[c] for c in text], dtype=np.int32)

    def dfa(self, p: int) -> DFA:
        """Crop pattern ``p`` back out of the bank as a standalone DFA."""
        n = int(self.n_states[p])
        return DFA(
            table=np.ascontiguousarray(self.tables[p, :n, :]),
            start=int(self.starts[p]),
            accepting=np.ascontiguousarray(self.accepting[p, :n]),
            alphabet=self.alphabet,
        )

    @classmethod
    def from_dfas(cls, dfas: Sequence[DFA], ids: Iterable[str] | None = None
                  ) -> "PatternBank":
        if not dfas:
            raise ValueError("empty pattern bank")
        alphabet = dfas[0].alphabet
        k = dfas[0].n_symbols
        for d in dfas:
            if d.alphabet != alphabet or d.n_symbols != k:
                raise ValueError("bank patterns must share one alphabet")
        n_max = max(d.n_states for d in dfas)
        p_count = len(dfas)
        tables = np.empty((p_count, n_max, k), dtype=np.int32)
        accepting = np.zeros((p_count, n_max), dtype=bool)
        # Self-loop padding: row j -> j for every symbol (see module docstring).
        pad_rows = np.repeat(np.arange(n_max, dtype=np.int32)[:, None], k, axis=1)
        for p, d in enumerate(dfas):
            tables[p] = pad_rows
            tables[p, : d.n_states] = d.table
            accepting[p, : d.n_states] = d.accepting
        return cls(
            tables=tables,
            accepting=accepting,
            starts=np.asarray([d.start for d in dfas], dtype=np.int32),
            n_states=np.asarray([d.n_states for d in dfas], dtype=np.int32),
            ids=tuple(ids) if ids is not None else tuple(
                f"pattern_{p}" for p in range(p_count)
            ),
            alphabet=alphabet,
        )

    @classmethod
    def from_patterns(cls, patterns: Mapping[str, str] | Sequence[str],
                      alphabet: str = AMINO_ACIDS) -> "PatternBank":
        """Compile PROSITE signatures (id -> pattern mapping, or a list)
        over ``alphabet``."""
        from .prosite import compile_prosite

        if isinstance(patterns, Mapping):
            ids = tuple(patterns.keys())
            pats = [patterns[i] for i in ids]
        else:
            ids = tuple(f"pattern_{p}" for p in range(len(patterns)))
            pats = list(patterns)
        return cls.from_dfas(
            [compile_prosite(p, alphabet=alphabet) for p in pats], ids)

    def device_arrays(self):
        """(tables, accepting, starts) as jnp arrays, ready for the matchers."""
        return (
            jnp.asarray(self.tables),
            jnp.asarray(self.accepting),
            jnp.asarray(self.starts),
        )


def bucket_by_size(dfas: Sequence[DFA], ids: Iterable[str] | None = None,
                   edges: Sequence[int] = (8, 16, 32, 64, 128, 256, 1024),
                   ) -> list:
    """Split patterns into size-bucketed banks to bound padding waste.

    One padded stack charges every pattern ``n_max``-wide gathers; real
    signature sets span two orders of magnitude in DFA size, so a single
    bank makes the small patterns pay for the largest one. Bucketing by
    state count (bucket ``i`` holds patterns with ``n <= edges[i]``) keeps
    per-bucket padding below ~2x while preserving the batched execution
    within each bucket. Returns the non-empty banks, smallest bucket first.

    The partition itself is :func:`repro.core.bucketing.partition_by_size`
    — the same helper batched construction buckets with.
    """
    from .bucketing import partition_by_size

    ids = list(ids) if ids is not None else [f"pattern_{p}" for p in range(len(dfas))]
    try:
        parts = partition_by_size([d.n_states for d in dfas], edges)
    except ValueError as e:
        raise ValueError(str(e).replace("item", "pattern", 1)) from None
    return [
        PatternBank.from_dfas([dfas[i] for i in idx], [ids[i] for i in idx])
        for _, idx in parts
    ]


def census_sequential(bank: PatternBank, corpus: np.ndarray) -> np.ndarray:
    """Reference census: plain per-pattern, per-sequence DFA loop (paper
    Fig. 1c applied P × D times). The differential-test oracle."""
    counts = np.zeros(bank.n_patterns, dtype=np.int32)
    for p in range(bank.n_patterns):
        d = bank.dfa(p)
        for row in np.asarray(corpus):
            counts[p] += bool(d.accepting[d.run(row)])
    return counts


