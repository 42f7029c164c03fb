"""Streaming input: corpora far larger than memory through the bank.

The paper's matching algorithm needs only each chunk's *transition function*
and an associative combine — nothing about it requires the whole input to be
resident. :class:`StreamSession` exploits that: callers feed arbitrary-sized
pieces (strings or encoded int arrays) of one logically-concatenated input;
the session buffers them into fixed-shape ``(n_chunks, block_len)`` chunk
blocks, pushes each block through the document path's executor for each
group's mode (``bank_doc_mappings`` / ``bank_doc_mappings_sfa`` or their
Pallas twins, and the speculative executor, on a one-document corpus), and
folds the block's transition function into a running function-monoid
prefix. Memory high-water mark is one block plus the ``(P, n)`` prefix,
independent of corpus length; fixed shapes mean every block reuses one
compiled program.

Every block round trip opens a ``scanner.device`` span with
``path="stream"``; ``engine.stream_blocks`` / ``engine.stream_residues``
count blocks and symbols, and speculative blocks move ``speculative.*``.

``StreamSession.finish()`` composes any ragged tail sequentially (exact,
< one block of work) and returns a :class:`StreamResult` whose mapping is
bit-identical to ``Scanner.mapping`` of the concatenated input.

The corpus-job path for long documents is its many-document form,
``Scanner.stream_docs``: :func:`repro.scanservice.scan_shard` sends every
document at or above the job's ``stream_threshold`` through it, block ``b``
of all of them in one round trip per group, so shard memory stays bounded
by one block per document regardless of document length.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import TYPE_CHECKING, Any

import numpy as np

from .. import obs
from ..speculative import SpeculationStats
from . import executors as X

if TYPE_CHECKING:  # pragma: no cover
    from .scanner import Scanner


@dataclass(frozen=True)
class StreamResult:
    """Outcome of a streamed scan over one concatenated input.

    ``mapping`` is the input's whole transition function — except when any
    pattern group ran speculatively, where it is ``None``: the speculative
    executor tracks exact *states*, not whole functions (that is the
    saving), so only ``final_states``/``accepted`` are available. They are
    bit-identical to the enumeration stream's; the corpus-job streaming
    path (:func:`repro.scanservice.scan_shard`) consumes ``accepted`` only.
    ``speculation`` carries the stream's aggregated
    :class:`~repro.speculative.SpeculationStats` (None without speculation).
    """

    mapping: np.ndarray | None  # (P, n_max) — None under speculation
    final_states: np.ndarray  # (P,) — exact final state per pattern
    accepted: np.ndarray      # (P,) bool
    n_symbols: int
    ids: tuple
    single: bool = False
    speculation: Any = None

    @property
    def accepts(self):
        """bool for a single-pattern scanner, (P,) bool for a bank."""
        return bool(self.accepted[0]) if self.single else self.accepted


class StreamSession:
    """Incremental (push-style) scan; create via ``Scanner.open_stream()``."""

    def __init__(self, scanner: "Scanner"):
        self.scanner = scanner
        pol = scanner.plan.chunking
        self.n_chunks = pol.n_chunks
        self.block_len = pol.block_len
        self.super_len = self.n_chunks * self.block_len
        self._buf = np.zeros(0, dtype=np.int32)
        self._n_symbols = 0
        self._finished = False
        # Running prefix per group: the function-monoid fold of everything
        # consumed so far, carried across block calls. Speculative groups
        # carry exact running *states* instead of whole functions — the
        # executor validates each block's chunks against them directly, so
        # the stream never pays the n-wide function composition.
        self._prefix = [
            np.broadcast_to(
                np.arange(g.n, dtype=np.int32), (len(g.indices), g.n)
            ).copy()
            for g in scanner.groups
        ]
        self._state = [
            g.bank.starts.astype(np.int32).copy()
            if g.mode == "speculative" else None
            for g in scanner.groups
        ]
        self._blocks = 0
        self._spec_stats: SpeculationStats | None = None
        self._has_spec = any(
            g.mode == "speculative" for g in scanner.groups
        )

    # -- feeding ------------------------------------------------------------

    def feed(self, piece) -> None:
        """Append one piece of the input (str or 1-D int array)."""
        if self._finished:
            raise RuntimeError("stream already finished")
        enc = (self.scanner.encode(piece) if isinstance(piece, str)
               else np.asarray(piece, dtype=np.int32))
        if enc.ndim != 1:
            raise ValueError("stream pieces must be 1-D (one input's symbols)")
        self._n_symbols += len(enc)
        self._buf = np.concatenate([self._buf, enc]) if len(self._buf) else enc
        while len(self._buf) >= self.super_len:
            block = self._buf[: self.super_len]
            self._buf = self._buf[self.super_len:]
            self._advance(block)

    @property
    def blocks(self) -> int:
        """Full blocks sent to the device so far."""
        return self._blocks

    def _advance(self, block: np.ndarray) -> None:
        """Fold one full (n_chunks * block_len) block into every group's
        running prefix (function or states): per group one round trip of
        the document path's executor for its mode, on a one-document
        corpus."""
        from .scanner import _count_speculation

        sc = self.scanner
        corpus = block[None, :]
        for gi, g in enumerate(sc.groups):
            if g.mode == "speculative":
                # The block's per-pattern entry states are the stream's
                # current ones; unresolved lanes fall back to enumeration
                # at them. The hot-state profile is memoized on the group
                # (advisory: staleness only costs repairs).
                finals, st = sc._speculative_finals(
                    g, sc._speculation_profile(g, corpus), corpus,
                    self._state[gi], stream=True)
                _count_speculation(st)
                self._state[gi] = finals[:, 0].astype(np.int32)
                self._spec_stats = st if self._spec_stats is None \
                    else self._spec_stats.merged(st)
                continue
            bm = sc._head_mappings(g, corpus, self.n_chunks,
                                   stream=True)[:, 0]        # (Pg, n)
            # combine(prefix, block): apply prefix first, then the block.
            self._prefix[gi] = np.take_along_axis(bm, self._prefix[gi], axis=1)
        self._blocks += 1
        obs.counter("engine.stream_blocks").inc()

    # -- finishing ----------------------------------------------------------

    def finish(self) -> StreamResult:
        """Compose the ragged tail, read off accepts, and close the stream."""
        if self._finished:
            raise RuntimeError("stream already finished")
        self._finished = True
        sc = self.scanner
        if len(self._buf):
            with obs.span("scanner.tail", docs=1, symbols=len(self._buf)):
                for gi, g in enumerate(sc.groups):
                    if g.mode == "speculative":
                        self._state[gi] = X.advance_states_sequential(
                            g.bank.tables, self._state[gi][:, None],
                            self._buf[None, :],
                        )[:, 0]
                    else:
                        self._prefix[gi] = X.compose_sequential(
                            g.bank.tables, self._prefix[gi], self._buf
                        )
            self._buf = np.zeros(0, dtype=np.int32)
        obs.counter("engine.stream_residues").inc(self._n_symbols)

        mapping = None if self._has_spec else np.broadcast_to(
            np.arange(sc.n_max, dtype=np.int32), (sc.n_patterns, sc.n_max)
        ).copy()
        final_states = np.zeros(sc.n_patterns, dtype=np.int32)
        accepted = np.zeros(sc.n_patterns, dtype=bool)
        for gi, g in enumerate(sc.groups):
            rows = np.arange(len(g.indices))
            if g.mode == "speculative":
                finals = self._state[gi]
            else:
                pref = self._prefix[gi]                      # (Pg, n_g)
                if mapping is not None:
                    mapping[g.indices, : g.n] = pref
                finals = pref[rows, g.bank.starts]
            final_states[g.indices] = finals
            accepted[g.indices] = g.bank.accepting[rows, finals]
        sc.last_speculation = self._spec_stats or sc.last_speculation
        return StreamResult(
            mapping=mapping,
            final_states=final_states,
            accepted=accepted,
            n_symbols=self._n_symbols,
            ids=sc.ids,
            single=sc.single,
            speculation=self._spec_stats,
        )
