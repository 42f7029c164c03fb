"""The ``Scanner`` facade: one entry point for every matching configuration.

``Scanner.compile(patterns, plan)`` accepts one pattern or a bank — a string
(PROSITE id, PROSITE signature, or framework regex), a compiled
:class:`~repro.core.dfa.DFA`, a :class:`~repro.core.multipattern.PatternBank`,
or a sequence/mapping of those — and a :class:`~repro.engine.plan.ScanPlan`
saying how to run. Compilation resolves each pattern's matching mode
(``auto`` attempts SFA construction under the plan's state budget — through
the content-addressed cache and the batched bank closure of
:mod:`repro.construction` — falling back to enumeration on
:class:`~repro.construction.StateBlowup`), stacks the
per-pattern tables into padded device arrays (stacked SFA deltas + mapping
lookups for SFA-mode patterns — the bank-axis version of the paper's
single-lookup inner loop), and returns a scanner exposing:

* ``scan(docs)``   — hit matrix of a document corpus against the bank;
* ``census(docs)`` — per-pattern hit counts (the ScanProsite census);
* ``stream(blocks)`` — corpora far larger than memory, fed as chunk blocks
  through the backend inner loop while the running function-monoid prefix
  carries across calls (see :mod:`repro.engine.streaming`);
* ``stream_docs(docs)`` — many long documents streamed side by side, one
  round trip per block step for all of them (the corpus job's long
  documents);
* ``mapping(doc)`` / ``accepts(doc)`` / ``locate(doc, pattern)`` helpers.

Every backend (``reference`` / ``xla`` / ``pallas``) and every mode computes
the same exact integer automaton semantics, so results are bit-identical
across all plans — the differential property the test suite pins down.
"""

from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass, field
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..compat import make_mesh
from ..construction import SFA, StateBlowup, construct_bank
from ..core.bucketing import partition_by_size
from ..core.dfa import DFA
from ..core.multipattern import PatternBank
from ..core.regex import AMINO_ACIDS
from ..speculative import (
    HotStateProfile,
    SpeculationStats,
    distributed_speculative_finals_fn,
    profile_hot_states,
    speculative_bank_finals,
    stack_profile_states,
)
from . import executors as X
from .plan import ChunkPolicy, ScanPlan
from .streaming import StreamResult, StreamSession

# /metrics HELP descriptions, registered once; hot paths increment by name.
obs.counter("engine.compiles", help="Scanner.compile calls")
obs.counter("engine.scans", help="Scanner scan/census calls")
obs.counter("engine.docs_scanned", help="documents scanned")
obs.counter("engine.residues_scanned",
            help="symbols of the documents scan() matched")
obs.counter("engine.h2d_bytes",
            help="bytes of scan inputs sent to the device")
obs.counter("engine.d2h_bytes",
            help="bytes of scan results read back from the device")
obs.counter("engine.enum_pattern_residues",
            help="pattern x residue steps the enumeration executor ran")
obs.counter("engine.stream_blocks",
            help="full (n_chunks, block_len) blocks stream() sent")
obs.counter("engine.stream_residues",
            help="symbols of the inputs stream() matched")
obs.counter("speculative.total_chunks",
            help="chunks executed speculatively")
obs.counter("speculative.hit_chunks",
            help="speculative chunks whose entry state was predicted")
obs.counter("speculative.repaired_chunks",
            help="misspeculated chunks re-scanned in the repair loop")
obs.counter("speculative.repair_rounds", help="repair rounds executed")
obs.counter("speculative.fallback_lanes",
            help="lanes handed to the exact enumeration fallback")
obs.gauge("speculative.hit_rate",
          help="speculation hit rate of the last scan")


# --------------------------------------------------------------------------
# Pattern normalization
# --------------------------------------------------------------------------


def _compile_one(spec: Any, alphabet: str) -> DFA:
    """One pattern spec -> DFA. Strings resolve, over ``alphabet``, as:
    bundled PROSITE id, then PROSITE signature syntax, then framework
    regex."""
    from ..core.dfa import compile_dfa
    from ..core.prosite import (
        PROSITE_EXTRA,
        PROSITE_SAMPLES,
        PrositeSyntaxError,
        compile_prosite,
    )

    if isinstance(spec, DFA):
        return spec
    if isinstance(spec, str):
        pool = {**PROSITE_SAMPLES, **PROSITE_EXTRA}
        if spec in pool:
            return compile_prosite(pool[spec], alphabet=alphabet)
        try:
            return compile_prosite(spec, alphabet=alphabet)
        except PrositeSyntaxError:
            return compile_dfa(spec, alphabet)
    raise TypeError(
        f"cannot compile pattern spec of type {type(spec).__name__}; "
        "expected str, DFA, PatternBank, or a sequence/mapping of those"
    )


def _normalize(patterns: Any, alphabet: str) -> tuple:
    """-> (ids, dfas, single) where ``single`` marks a one-pattern input;
    strings compile over ``alphabet``."""
    if isinstance(patterns, PatternBank):
        return (tuple(patterns.ids),
                [patterns.dfa(p) for p in range(patterns.n_patterns)], False)
    if isinstance(patterns, (str, DFA)):
        dfa = _compile_one(patterns, alphabet)
        pid = patterns if isinstance(patterns, str) else "pattern_0"
        return (pid,), [dfa], True
    if isinstance(patterns, Mapping):
        ids = tuple(patterns.keys())
        return ids, [_compile_one(patterns[i], alphabet) for i in ids], False
    if isinstance(patterns, Sequence):
        dfas = [_compile_one(p, alphabet) for p in patterns]
        ids = tuple(
            p if isinstance(p, str) else f"pattern_{i}"
            for i, p in enumerate(patterns)
        )
        return ids, dfas, False
    raise TypeError(f"cannot build a Scanner from {type(patterns).__name__}")


# --------------------------------------------------------------------------
# Compiled pattern groups
# --------------------------------------------------------------------------


@dataclass
class PatternGroup:
    """One homogeneous slice of the compiled bank: same mode, one padded
    table stack (and, for SFA mode, one stacked delta + mapping pair)."""

    indices: np.ndarray          # positions in the scanner's pattern order
    bank: PatternBank            # sub-bank (enumeration tables, padded)
    mode: str                    # "sfa" | "enumeration" | "speculative"
    tables: Any = None           # (Pg, n, k) jnp — enumeration tables
    deltas: Any = None           # (Pg, S, k) jnp — stacked SFA tables
    sfa_maps: Any = None         # (Pg, S, n) jnp — SFA state -> mapping
    sfa_states: np.ndarray | None = None  # (Pg,) true SFA state counts
    _dist_fn: Any = field(default=None, repr=False)
    _spec_dist_fn: Any = field(default=None, repr=False)
    _spec_profile: Any = field(default=None, repr=False)  # memoized (Pg, m)

    @property
    def n(self) -> int:
        return self.bank.n_max


def _stack_sfas(sfas: Sequence[SFA], n_max: int) -> tuple:
    """Stack per-pattern SFAs into padded (P, S_max, k) + (P, S_max, n_max).

    The padding story mirrors ``PatternBank``: delta rows ``s >= S_i`` are
    self-loops (inert, gathers stay in range) and mapping rows/columns pad
    with the identity, so an SFA-mode chunk function equals the enumeration
    chunk function on the padded layout entry for entry.
    """
    S_max = max(s.n_states for s in sfas)
    k = sfas[0].delta.shape[1]
    Pg = len(sfas)
    deltas = np.empty((Pg, S_max, k), dtype=np.int32)
    maps = np.empty((Pg, S_max, n_max), dtype=np.int32)
    pad_rows = np.repeat(np.arange(S_max, dtype=np.int32)[:, None], k, axis=1)
    ident = np.arange(n_max, dtype=np.int32)
    for p, s in enumerate(sfas):
        S_i = s.n_states
        n_i = s.mappings.shape[1]
        deltas[p] = pad_rows
        deltas[p, :S_i] = s.delta
        maps[p] = ident
        maps[p, :S_i, :n_i] = s.mappings
        maps[p, :S_i, n_i:] = ident[n_i:]
    return deltas, maps, np.asarray([s.n_states for s in sfas], dtype=np.int32)


def _size_partition(sizes: Sequence[int], edges: Sequence[int]):
    """Partition indices by size buckets (bucket i holds sizes <= edges[i]);
    oversized items land in one overflow bucket rather than erroring."""
    return [
        idx for _, idx in partition_by_size(sizes, edges, overflow="extend")
    ]


# --------------------------------------------------------------------------
# Construction resolution (cache + bank rounds)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class ConstructionReport:
    """What ``Scanner.compile`` did to obtain its SFAs.

    ``rounds`` is zero when every pattern was answered by the cache — the
    "recompiling the same patterns performs zero construction rounds"
    contract the cache tests assert.
    """

    rounds: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    constructed: int = 0
    blown: int = 0
    method: str = "none"
    retries: int = 0


def _resolve_sfas(ids, dfas, plan: ScanPlan):
    """Per-pattern mode resolution: cache lookups first, then one bank
    construction for the misses. -> (modes, {index: SFA}, report)."""
    P = len(dfas)
    if plan.mode == "enumeration":
        return ["enumeration"] * P, {}, ConstructionReport()
    if plan.mode == "speculative":
        # Forced speculation needs no SFA construction at all — the whole
        # point of the mode is serving patterns the n^n bound locks out.
        return ["speculative"] * P, {}, ConstructionReport()

    policy = plan.construction
    budget = plan.sfa_state_budget
    cache = policy.resolve_cache()

    def fallback(i):
        if plan.mode == "sfa":
            raise StateBlowup(
                f"pattern {ids[i]!r}: SFA exceeds the "
                f"{budget}-state budget and "
                "mode='sfa' forbids the enumeration fallback"
            ) from None
        # auto's blowup tier: large automata go speculative (tracking all n
        # states per chunk is what speculation exists to avoid); small
        # blowup patterns keep the enumeration fallback.
        if dfas[i].n_states >= plan.speculation.auto_states:
            return "speculative"
        return "enumeration"

    modes: list = [None] * P
    sfas: dict = {}
    hits = misses = 0
    need = []
    for i, d in enumerate(dfas):
        kind, sfa = (None, None) if cache is None else cache.lookup(
            d, max_states=budget
        )
        if kind == "sfa":
            hits += 1
            sfas[i], modes[i] = sfa, "sfa"
        elif kind == "blowup":
            hits += 1
            modes[i] = fallback(i)
        else:
            misses += 1
            need.append(i)

    rounds = retries = blown_count = 0
    method = "none"
    if need:
        method = policy.method
        if method == "auto":
            # A bank round only pays once the missing set amortizes its XLA
            # compilation; small miss sets close faster on the NumPy loop.
            method = "batched" if len(need) >= 4 else "loop"
        result = construct_bank(
            [dfas[i] for i in need],
            max_states=budget,
            tile=policy.tile,
            max_retries=policy.max_retries,
            method=method,
            engine=policy.engine,
            distribution=policy.distribution,
            mesh=policy.mesh,
            pattern_axis=policy.pattern_axis,
            fingerprint_backend=policy.fingerprint_backend,
            expand_backend=policy.expand_backend,
            bucketing=policy.bucketing,
            bucket_growth=policy.bucket_growth,
        )
        rounds = result.stats.rounds
        retries = int(np.sum(result.stats.retries))
        for j, i in enumerate(need):
            if result.blown[j]:
                blown_count += 1
                if cache is not None:
                    cache.store_blowup(dfas[i], budget)
                modes[i] = fallback(i)
            else:
                sfas[i] = result.sfas[j]
                modes[i] = "sfa"
                if cache is not None:
                    cache.store(dfas[i], result.sfas[j])
    report = ConstructionReport(
        rounds=rounds, cache_hits=hits, cache_misses=misses,
        constructed=len(need) - blown_count, blown=blown_count,
        method=method, retries=retries,
    )
    return modes, sfas, report


# --------------------------------------------------------------------------
# Scan results
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class ScanResult:
    """Hit matrix of a scan: ``hits[p, d]`` iff doc ``d`` matches pattern ``p``.

    ``speculation`` carries the scan's aggregated
    :class:`~repro.speculative.SpeculationStats` when any pattern group ran
    speculatively (None otherwise) — the per-scan hit-rate/repair report.
    """

    hits: np.ndarray      # (P, D) bool
    ids: tuple
    speculation: Any = None

    @property
    def counts(self) -> np.ndarray:
        """Per-pattern hit counts (the census row), (P,) int32."""
        return np.sum(self.hits, axis=1, dtype=np.int32)

    def by_id(self) -> dict:
        return {pid: self.hits[p] for p, pid in enumerate(self.ids)}


#: [h2d, d2h] bytes of the ``scan`` call running in this context (None
#: outside one): ``_count_moved`` adds to it, ``scan`` puts it on its span.
_scan_moved: contextvars.ContextVar = contextvars.ContextVar(
    "repro_scan_moved", default=None
)


def _stream_attrs(stream: bool) -> dict:
    """A stream block's round trips carry ``path="stream"``; the scan's
    spans carry no ``path``."""
    return {"path": "stream"} if stream else {}


def _count_moved(span, h2d: int, d2h: int) -> None:
    """One device round trip's bytes, on its ``scanner.device`` span, on the
    registry's ``engine.h2d_bytes`` / ``engine.d2h_bytes``, and on the
    enclosing ``scan`` call's totals."""
    obs.counter("engine.h2d_bytes").inc(h2d)
    obs.counter("engine.d2h_bytes").inc(d2h)
    if span is not None:
        span.attrs.update(h2d_bytes=h2d, d2h_bytes=d2h)
    total = _scan_moved.get()
    if total is not None:
        total[0] += h2d
        total[1] += d2h


def _count_enumeration(span, pattern_residues: int) -> None:
    """One enumeration round trip's work, ``patterns x docs x head
    length``, on its ``scanner.device`` span and on the registry's
    ``engine.enum_pattern_residues``."""
    obs.counter("engine.enum_pattern_residues").inc(pattern_residues)
    if span is not None:
        span.attrs.update(pattern_residues=pattern_residues)


def _count_speculation(stats: SpeculationStats) -> None:
    """One speculative pass's stats on the registry's ``speculative.*``."""
    obs.counter("speculative.total_chunks").inc(stats.total_chunks)
    obs.counter("speculative.hit_chunks").inc(stats.hit_chunks)
    obs.counter("speculative.repaired_chunks").inc(stats.repaired_chunks)
    obs.counter("speculative.repair_rounds").inc(stats.repair_rounds)
    obs.counter("speculative.fallback_lanes").inc(stats.fallback_lanes)
    if stats.total_chunks:
        obs.gauge("speculative.hit_rate").set(stats.hit_rate)


@contextlib.contextmanager
def _scan_totals():
    """Collect the bytes moved inside the block -> [h2d, d2h]."""
    total = [0, 0]
    token = _scan_moved.set(total)
    try:
        yield total
    finally:
        _scan_moved.reset(token)


# --------------------------------------------------------------------------
# The facade
# --------------------------------------------------------------------------


class Scanner:
    """A compiled multi-pattern scan engine. Build with :meth:`compile`."""

    def __init__(self, ids, dfas, groups, plan, single, mesh,
                 construction_report: ConstructionReport | None = None):
        self.ids = ids
        self.plan = plan
        self.groups = groups
        self.single = single
        self.mesh = mesh
        self.construction_report = construction_report or ConstructionReport()
        self.alphabet = dfas[0].alphabet
        self.n_patterns = len(dfas)
        self.n_max = max(d.n_states for d in dfas)
        self.starts = np.asarray([d.start for d in dfas], dtype=np.int32)
        self._dfas = dfas
        self.last_speculation: SpeculationStats | None = None
        #: trace id of the last traced compile/scan through this scanner —
        #: the key ``obs.trace_summary`` (and ``describe``) correlates on.
        self.last_trace_id: str | None = None
        self.pattern_modes = {}
        for g in groups:
            for i in g.indices:
                self.pattern_modes[ids[i]] = g.mode

    # -- compilation --------------------------------------------------------

    @classmethod
    def compile(cls, patterns: Any, plan: ScanPlan | None = None,
                alphabet: str | None = None, **overrides) -> "Scanner":
        """Compile patterns under a plan (``overrides`` patch plan fields,
        so ``Scanner.compile(bank, mode="sfa")`` works without a ScanPlan).
        String patterns compile over ``alphabet`` (default: the 20 amino
        acids); compiled DFAs and banks must already be over it."""
        plan = (plan or ScanPlan()).with_(**overrides) if overrides else \
            (plan or ScanPlan()).validate()
        ids, dfas, single = _normalize(patterns, alphabet or AMINO_ACIDS)
        if not dfas:
            raise ValueError("empty pattern set")
        alphabet = alphabet or dfas[0].alphabet
        for d in dfas:
            if d.alphabet != alphabet:
                raise ValueError(
                    f"all patterns must share one alphabet ({alphabet!r}); "
                    f"got one over {d.alphabet!r}")

        trace_id = None
        with obs.span("scanner.compile", patterns=len(dfas),
                      mode=plan.mode, backend=plan.backend):
            trace_id = obs.current_trace_id()
            # Resolve per-pattern mode. ``auto`` = the paper's criterion:
            # use the SFA when construction closes under the budget,
            # enumeration when it blows up (Mytkowicz-style fallback).
            # Construction goes through the content-addressed cache + the
            # batched bank closure (see repro.construction): recompiling
            # the same patterns is free.
            modes, sfas, report = _resolve_sfas(ids, dfas, plan)

            mesh = None
            if plan.distribution == "shard_map":
                mesh = plan.mesh if plan.mesh is not None else make_mesh(
                    (jax.device_count(),), (plan.data_axis,)
                )

            groups = []
            for mode in ("sfa", "enumeration", "speculative"):
                member = [i for i, m in enumerate(modes) if m == mode]
                if not member:
                    continue
                if plan.chunking.bucket:
                    sizes = [
                        sfas[i].n_states if mode == "sfa"
                        else dfas[i].n_states
                        for i in member
                    ]
                    parts = _size_partition(sizes, plan.chunking.bucket_edges)
                    parts = [[member[j] for j in p] for p in parts]
                else:
                    parts = [member]
                for part in parts:
                    groups.append(cls._build_group(
                        part, [dfas[i] for i in part], [ids[i] for i in part],
                        mode, [sfas.get(i) for i in part], plan, mesh,
                    ))
        obs.counter("engine.compiles").inc()
        scanner = cls(ids, dfas, groups, plan, single, mesh, report)
        scanner.last_trace_id = trace_id
        return scanner

    @staticmethod
    def _build_group(indices, dfas, gids, mode, sfas, plan, mesh) -> PatternGroup:
        bank = PatternBank.from_dfas(dfas, gids)
        g = PatternGroup(
            indices=np.asarray(indices, dtype=np.int64), bank=bank, mode=mode
        )
        g.tables = jnp.asarray(bank.tables)
        if mode == "sfa":
            deltas, maps, sizes = _stack_sfas(sfas, bank.n_max)
            g.deltas = jnp.asarray(deltas)
            g.sfa_maps = jnp.asarray(maps)
            g.sfa_states = sizes
        if mesh is not None:
            g._dist_fn = X.distributed_doc_mappings_fn(
                mesh, plan.data_axis, plan.chunking.n_chunks,
                sfa_mode=(mode == "sfa"),
            )
            if mode == "speculative":
                g._spec_dist_fn = distributed_speculative_finals_fn(
                    mesh, plan.data_axis, plan.chunking.n_chunks,
                    plan.speculation.max_repair_rounds,
                )
        return g

    # -- encoding helpers ---------------------------------------------------

    def encode(self, text: str) -> np.ndarray:
        sym = {c: i for i, c in enumerate(self.alphabet)}
        try:
            return np.asarray([sym[c] for c in text], dtype=np.int32)
        except KeyError as e:
            raise ValueError(f"symbol {e.args[0]!r} is not in the scanner's "
                             f"alphabet {self.alphabet!r}") from None

    def _encode_docs(self, docs) -> list:
        if isinstance(docs, str):
            docs = [docs]
        if isinstance(docs, np.ndarray) and docs.ndim == 2:
            return [np.asarray(row, dtype=np.int32) for row in docs]
        out = []
        for d in docs:
            out.append(self.encode(d) if isinstance(d, str)
                       else np.asarray(d, dtype=np.int32))
        return out

    # -- the chunk-function core -------------------------------------------

    def _group_doc_mappings(self, g: PatternGroup, corpus: np.ndarray
                            ) -> np.ndarray:
        """Final mapping of every (pattern-in-group, doc): -> (Pg, D, n).

        The chunk-parallel backend handles the head (the largest prefix
        divisible by ``n_chunks``); any ragged tail is composed sequentially
        in NumPy — cheap (< one chunk per doc) and exact.
        """
        n_chunks = self.plan.chunking.n_chunks
        D, L = corpus.shape
        head_len = L - (L % n_chunks)
        Pg, n = len(g.indices), g.n

        if head_len:
            head = corpus[:, :head_len]
            maps = self._head_mappings(g, head, n_chunks)
        else:
            maps = np.broadcast_to(
                np.arange(n, dtype=np.int32), (Pg, D, n)
            ).copy()

        if head_len < L:
            if not maps.flags.writeable:
                maps = maps.copy()
            with obs.span("scanner.tail", docs=D, symbols=L - head_len):
                for d in range(D):
                    maps[:, d, :] = X.compose_sequential(
                        g.bank.tables, maps[:, d, :], corpus[d, head_len:]
                    )
        return maps

    def _device_mesh(self, D: int, stream: bool):
        """The mesh a round trip of ``D`` docs runs on: the plan's for the
        scan, none for a stream block (one device's worth of work)."""
        if stream or self.mesh is None:
            return None
        n_dev = int(np.prod(list(self.mesh.shape.values())))
        if D % n_dev:
            raise ValueError(
                f"shard_map distribution needs doc count ({D}) divisible "
                f"by the mesh's {self.plan.data_axis} size ({n_dev})"
            )
        return self.mesh

    def _head_mappings(self, g: PatternGroup, head: np.ndarray,
                       n_chunks: int, stream: bool = False) -> np.ndarray:
        """Chunk-parallel mappings of the head: one ``scanner.device`` span
        from the upload to the read-back (none on the reference backend,
        which never leaves the host). A stream block's span carries
        ``path="stream"``."""
        backend = self.plan.backend
        if backend == "reference":
            return _reference_doc_mappings(g.bank.tables, head)
        D, L = head.shape
        mesh = self._device_mesh(D, stream)
        with obs.span("scanner.device", mode=g.mode, patterns=len(g.indices),
                      docs=D, length=L, **_stream_attrs(stream)) as sp:
            corpus_j = jnp.asarray(head)
            if mesh is not None:
                if g.mode == "sfa":
                    out = g._dist_fn(g.deltas, g.sfa_maps, corpus_j)
                else:
                    out = g._dist_fn(g.tables, corpus_j)
            elif backend == "pallas":
                if g.mode == "sfa":
                    out = X.bank_doc_mappings_sfa_pallas(
                        g.deltas, g.sfa_maps, corpus_j, n_chunks
                    )
                else:
                    out = X.bank_doc_mappings_pallas(g.tables, corpus_j,
                                                     n_chunks)
            elif g.mode == "sfa":
                out = X.bank_doc_mappings_sfa(g.deltas, g.sfa_maps, corpus_j,
                                              n_chunks)
            else:
                out = X.bank_doc_mappings(g.tables, corpus_j, n_chunks)
            maps = np.asarray(out)
            _count_moved(sp, head.nbytes, maps.nbytes)
            if g.mode == "enumeration":
                _count_enumeration(sp, len(g.indices) * D * L)
        return maps

    # -- the speculative core ----------------------------------------------

    def _speculation_sample(self, corpus: np.ndarray) -> np.ndarray:
        """The profiler's symbol sample: a prefix of the flattened corpus
        sized by the policy's ``sample_frac`` / ``max_sample``."""
        pol = self.plan.speculation
        flat = corpus.reshape(-1)
        s = min(pol.max_sample, max(1, int(pol.sample_frac * flat.size)))
        return flat[:s]

    def _explicit_profile_states(self, g: PatternGroup, src) -> np.ndarray:
        """Explicit ``profile_source``: a mapping {pattern id: states} or one
        state sequence for every pattern. The adversarial-testing hook — any
        states are *correct* (misspeculation only costs repairs)."""
        pol = self.plan.speculation
        if hasattr(src, "keys"):
            rows = []
            for i in g.indices:
                pid = self.ids[i]
                if pid not in src:
                    raise ValueError(
                        f"explicit speculation profile is missing pattern "
                        f"{pid!r}"
                    )
                rows.append(np.asarray(src[pid], dtype=np.int32))
        else:
            rows = [np.asarray(src, dtype=np.int32)] * len(g.indices)
        for r in rows:
            if r.ndim != 1 or not r.size:
                raise ValueError(
                    "explicit speculation profiles must be non-empty 1-D "
                    "state sequences"
                )
        profs = [
            HotStateProfile(
                states=r, weights=np.zeros(len(r), dtype=np.float64),
                sample_len=0,
            )
            for r in rows
        ]
        return stack_profile_states(profs, pol.m, g.n)

    def _speculation_profile(self, g: PatternGroup, corpus: np.ndarray
                             ) -> np.ndarray:
        """Resolve one group's (Pg, m) speculated boundary states.

        ``"sample"`` profiles the first scanned corpus (a bounded
        ``max_sample``-symbol walk) and memoizes the result on the group:
        the profiler is a sequential host-side pass, and paying it once per
        *scanner* instead of once per scan is what keeps speculation ahead
        of enumeration on repeat scans. A profile is advisory — reusing it
        on later, differently-distributed corpora costs repair rounds,
        never correctness. ``"store"`` consults the plan's persistent
        :class:`~repro.scanservice.ArtifactStore` by ``dfa_cache_key``
        first, samples on a miss, and persists what it learned; explicit
        sources bypass profiling entirely.
        """
        pol = self.plan.speculation
        src = pol.profile_source
        if not isinstance(src, str):
            return self._explicit_profile_states(g, src)
        if g._spec_profile is not None:
            return g._spec_profile
        store = self.plan.construction.resolve_store() if src == "store" \
            else None
        profiles: list = [None] * len(g.indices)
        keys = None
        if store is not None and hasattr(store, "get_profile"):
            from ..construction import dfa_cache_key

            keys = [dfa_cache_key(self._dfas[i]) for i in g.indices]
            for j, key in enumerate(keys):
                meta = store.get_profile(key)
                if meta is not None:
                    profiles[j] = HotStateProfile.from_json(meta)
        need = [j for j, pr in enumerate(profiles) if pr is None]
        if need:
            sample = self._speculation_sample(corpus)
            fresh = profile_hot_states(
                g.bank.tables[need], g.bank.starts[need], sample, pol.m
            )
            for j, pr in zip(need, fresh):
                profiles[j] = pr
                if keys is not None and hasattr(store, "put_profile"):
                    store.put_profile(keys[j], pr.to_json())
        states = stack_profile_states(profiles, pol.m, g.n)
        g._spec_profile = states
        return states

    def _speculative_finals(self, g: PatternGroup, spec: np.ndarray,
                            head: np.ndarray, entry: np.ndarray,
                            stream: bool = False) -> tuple:
        """Exact final states of every (pattern-in-group, doc) of ``head``
        (a multiple of ``n_chunks`` long) from the ``entry`` states, (Pg,)
        per pattern or (Pg, D) per lane, speculating on the (Pg, m) states
        ``spec``: (Pg, D) int32 plus the pass's
        :class:`~repro.speculative.SpeculationStats`.

        The executor only adopts chunk results whose entry state it verified
        exactly; any lane the repair bound leaves unresolved is recomputed
        through the enumeration executor (always the local XLA one —
        exactness makes the backend choice invisible, and the fallback
        subset's ragged doc count doesn't fit the mesh contract). A stream
        block's device spans carry ``path="stream"``, and its speculative
        one the chunk counts too.
        """
        pol = self.plan.speculation
        n_chunks = self.plan.chunking.n_chunks
        D, L = head.shape
        Pg = len(g.indices)
        entry = np.asarray(entry, dtype=np.int32)
        mesh = self._device_mesh(D, stream)
        attrs = _stream_attrs(stream)
        with obs.span("scanner.device", mode=g.mode, patterns=Pg, docs=D,
                      length=L, **attrs) as sp:
            args = (jnp.asarray(spec), jnp.asarray(entry), jnp.asarray(head))
            if mesh is not None:
                out = g._spec_dist_fn(g.tables, *args)
            else:
                out = speculative_bank_finals(
                    g.tables, *args, n_chunks=n_chunks,
                    max_rounds=pol.max_repair_rounds,
                )
            out = [np.asarray(x) for x in out]
            _count_moved(sp, spec.nbytes + entry.nbytes + head.nbytes,
                         sum(x.nbytes for x in out))
            finals, resolved, hit_n, repaired, rounds = out
            stats = SpeculationStats(
                total_chunks=Pg * D * n_chunks,
                hit_chunks=int(hit_n),
                repaired_chunks=int(repaired),
                repair_rounds=int(rounds),
                fallback_lanes=int(np.sum(~resolved)),
            )
            if sp is not None and stream:
                sp.attrs.update(total_chunks=stats.total_chunks,
                                hit_chunks=stats.hit_chunks)
        if not resolved.all():
            finals = np.array(finals)  # device views are read-only
            bad = np.flatnonzero(~resolved.all(axis=0))
            sub = np.ascontiguousarray(head[bad])
            with obs.span("speculative.fallback", lanes=len(bad)), \
                    obs.span("scanner.device", mode="enumeration",
                             patterns=Pg, docs=len(bad), length=L,
                             **attrs) as sp:
                maps = np.asarray(X.bank_doc_mappings(
                    g.tables, jnp.asarray(sub), n_chunks,
                ))
                _count_moved(sp, sub.nbytes, maps.nbytes)
                _count_enumeration(sp, Pg * len(bad) * L)
            lane_entry = np.broadcast_to(entry.reshape(Pg, -1), (Pg, D))
            exact = np.take_along_axis(
                maps, lane_entry[:, bad, None].astype(np.int64), axis=2
            )[:, :, 0]
            finals[:, bad] = np.where(resolved[:, bad], finals[:, bad], exact)
        return finals, stats

    def _group_doc_finals(self, g: PatternGroup, corpus: np.ndarray) -> tuple:
        """Speculative path: exact final states of every (pattern-in-group,
        doc) from each pattern's start — (Pg, D) int32 plus the group's
        :class:`~repro.speculative.SpeculationStats`, under one
        ``speculative.scan`` span that carries the chunk counts.

        Bit-identical to reading the enumeration mappings off at the start
        states (see :meth:`_speculative_finals`). The ragged tail advances
        the finals sequentially, mirroring ``_group_doc_mappings``.
        """
        n_chunks = self.plan.chunking.n_chunks
        D, L = corpus.shape
        head_len = L - (L % n_chunks)
        starts = g.bank.starts.astype(np.int32)
        stats = SpeculationStats()
        with obs.span("speculative.scan", patterns=len(g.indices),
                      docs=D) as sp:
            if head_len:
                finals, stats = self._speculative_finals(
                    g, self._speculation_profile(g, corpus),
                    corpus[:, :head_len], starts)
            else:
                finals = np.repeat(starts[:, None], D, axis=1)
            if head_len < L:
                with obs.span("scanner.tail", docs=D, symbols=L - head_len):
                    finals = X.advance_states_sequential(
                        g.bank.tables, finals, corpus[:, head_len:]
                    )
            if sp is not None:
                sp.attrs.update(total_chunks=stats.total_chunks,
                                hit_chunks=stats.hit_chunks)
        _count_speculation(stats)
        return finals, stats

    # -- public scan API ----------------------------------------------------

    def scan(self, docs) -> ScanResult:
        """Match a corpus against the bank -> :class:`ScanResult` (P, D)."""
        spec_stats: SpeculationStats | None = None
        with obs.span("scanner.scan", patterns=self.n_patterns) as sp, \
                _scan_totals() as moved:
            self.last_trace_id = obs.current_trace_id() or self.last_trace_id
            with obs.span("scanner.encode"):
                enc = self._encode_docs(docs)
            D = len(enc)
            residues = sum(len(e) for e in enc)
            hits = np.zeros((self.n_patterns, D), dtype=bool)
            # Batch docs of equal length together (one fixed-shape program
            # each).
            by_len: dict = {}
            for d, e in enumerate(enc):
                by_len.setdefault(len(e), []).append(d)
            for L, idxs in sorted(by_len.items()):
                with obs.span("scanner.stack", docs=len(idxs), length=L):
                    corpus = np.stack([enc[d] for d in idxs]) if L else \
                        np.zeros((len(idxs), 0), dtype=np.int32)
                for g in self.groups:
                    maps = None
                    if g.mode == "speculative" and L:
                        finals, st = self._group_doc_finals(g, corpus)
                        spec_stats = st if spec_stats is None \
                            else spec_stats.merged(st)
                    elif L:
                        maps = self._group_doc_mappings(g, corpus)
                    else:
                        maps = np.broadcast_to(
                            np.arange(g.n, dtype=np.int32),
                            (len(g.indices), len(idxs), g.n),
                        )
                    with obs.span("scanner.select", patterns=len(g.indices),
                                  docs=len(idxs)):
                        if maps is not None:
                            starts = g.bank.starts              # (Pg,)
                            finals = np.take_along_axis(
                                maps, starts[:, None, None].astype(np.int64),
                                axis=2
                            )[:, :, 0]                          # (Pg, Dg)
                        acc = np.take_along_axis(
                            g.bank.accepting, finals.astype(np.int64), axis=1
                        )
                        hits[np.ix_(g.indices, np.asarray(idxs))] = acc
            if sp is not None:
                sp.attrs.update(docs=D, residues=residues,
                                h2d_bytes=moved[0], d2h_bytes=moved[1])
        obs.counter("engine.scans").inc()
        obs.counter("engine.docs_scanned").inc(D)
        obs.counter("engine.residues_scanned").inc(residues)
        self.last_speculation = spec_stats
        return ScanResult(hits=hits, ids=self.ids, speculation=spec_stats)

    def census(self, docs) -> np.ndarray:
        """Per-pattern hit counts over a corpus, (P,) int32."""
        return self.scan(docs).counts

    def census_windows(self, seq, window: int, stride: int | None = None
                       ) -> ScanResult:
        """Prefix-scan census of all sliding windows of one sequence.

        ``scan`` on materialized windows recomputes every shared symbol's
        chunk function once per overlapping window; here the sequence is cut
        into ``stride``-symbol blocks, each block's transition function is
        computed **once**, and all window compositions come out of two
        :func:`repro.core.monoid.scan` passes per tile
        (:func:`repro.engine.executors.sliding_window_mappings`). Function
        composition is exactly associative, so ``hits`` is bit-identical to
        ``scan([seq[i*stride : i*stride + window] for i ...])``.

        ``stride`` must divide ``window`` (default: ``stride = window``,
        i.e. disjoint blocks). Returns a :class:`ScanResult` whose "docs"
        are the ``(len(seq) - window) // stride + 1`` full windows.
        """
        stride = window if stride is None else stride
        if window < 1 or stride < 1:
            raise ValueError("window and stride must be >= 1")
        if window % stride:
            raise ValueError(
                f"stride ({stride}) must divide window ({window}): the "
                "prefix-scan census composes whole stride-blocks"
            )
        enc = self._encode_docs([seq])[0]
        L = len(enc)
        m = window // stride
        W = (L - window) // stride + 1 if L >= window else 0
        hits = np.zeros((self.n_patterns, W), dtype=bool)
        if W == 0:
            return ScanResult(hits=hits, ids=self.ids)
        B = W + m - 1
        blocks = np.ascontiguousarray(enc[: B * stride].reshape(B, stride))
        if self.mesh is not None:
            # Blocks are the "docs" of the shard_map path: pad the block
            # axis up to the mesh size with throwaway rows, cropped below.
            n_dev = int(np.prod(list(self.mesh.shape.values())))
            pad_rows = -B % n_dev
            if pad_rows:
                blocks = np.concatenate(
                    [blocks, np.zeros((pad_rows, stride), dtype=np.int32)]
                )
        for g in self.groups:
            maps = self._group_doc_mappings(g, blocks)[:, :B]  # (Pg, B, n)
            with obs.span("scanner.device", mode="windows",
                          patterns=len(g.indices), docs=W, length=window
                          ) as sp:
                wmaps = np.asarray(X.sliding_window_mappings(
                    jnp.asarray(maps), m
                ))                                          # (Pg, W, n)
                _count_moved(sp, maps.nbytes, wmaps.nbytes)
            finals = np.take_along_axis(
                wmaps, g.bank.starts[:, None, None].astype(np.int64), axis=2
            )[:, :, 0]
            acc = np.take_along_axis(
                g.bank.accepting, finals.astype(np.int64), axis=1
            )
            hits[g.indices, :] = acc
        return ScanResult(hits=hits, ids=self.ids)

    def mapping(self, doc) -> np.ndarray:
        """Transition function of one whole input under every pattern,
        (P, n_max) int32 on the scanner's padded layout (identity beyond
        each pattern's true state count).

        Speculative-mode groups compute their mapping through the
        enumeration executor here: a full transition function inherently
        needs all n states, so there is nothing for speculation to skip.
        ``scan``/``stream`` are the speculative fast paths.
        """
        enc = self._encode_docs([doc])[0]
        out = np.broadcast_to(
            np.arange(self.n_max, dtype=np.int32),
            (self.n_patterns, self.n_max),
        ).copy()
        corpus = enc[None, :]
        for g in self.groups:
            maps = self._group_doc_mappings(g, corpus)[:, 0, :]  # (Pg, n_g)
            out[g.indices, : g.n] = maps
        return out

    def accepts(self, doc):
        """Accept flags of one input: bool for a single-pattern scanner,
        (P,) bool for a bank."""
        flags = self.scan([doc]).hits[:, 0]
        return bool(flags[0]) if self.single else flags

    def locate(self, doc, pattern=None) -> np.ndarray:
        """Per-position accept flags of one doc under one pattern (two-pass
        chunk-parallel match localization). ``pattern`` is an id or index;
        defaults to the only pattern of a single-pattern scanner."""
        if pattern is None:
            if not self.single:
                raise ValueError("bank scanner: pass pattern=<id or index>")
            p = 0
        else:
            p = (self.ids.index(pattern) if isinstance(pattern, str)
                 else int(pattern))
        d = self._dfas[p]
        enc = self._encode_docs([doc])[0]
        n_chunks = self.plan.chunking.n_chunks
        head_len = len(enc) - (len(enc) % n_chunks)
        flags = np.zeros(len(enc), dtype=bool)
        if head_len:
            flags[:head_len] = np.asarray(X.find_matches_parallel(
                jnp.asarray(d.table), jnp.asarray(d.accepting),
                jnp.asarray(enc[:head_len]), d.start, n_chunks,
            ))
        # sequential tail from the head's final state
        s = d.run(enc[:head_len]) if head_len else d.start
        for i in range(head_len, len(enc)):
            s = int(d.table[s, enc[i]])
            flags[i] = bool(d.accepting[s])
        return flags

    # -- serving ------------------------------------------------------------

    @classmethod
    def service(cls, store_dir=None, plan: ScanPlan | None = None,
                **kwargs):
        """The serving layer's front door: a
        :class:`repro.scanservice.ScanService` whose compiles run through a
        persistent artifact store at ``store_dir`` (when given) and whose
        ``submit``/``flush`` coalesce concurrent requests into one bank
        compile + one fused scan. See :mod:`repro.scanservice`.
        """
        from ..scanservice import ScanService

        return ScanService(store_dir=store_dir, plan=plan, **kwargs)

    # -- streaming ----------------------------------------------------------

    def open_stream(self) -> StreamSession:
        """Push API: feed chunk blocks incrementally, then ``finish()``."""
        return StreamSession(self)

    def stream(self, blocks) -> StreamResult:
        """Scan one logically-concatenated input delivered as an iterable of
        blocks (strings or encoded int arrays) without whole-corpus
        residency. Equivalent to ``scan`` on the concatenation; the running
        function-monoid prefix carries across fixed-shape block calls. One
        ``scanner.stream`` span holds the input's round trips and tail."""
        with obs.span("scanner.stream") as sp, _scan_totals() as moved:
            sess = self.open_stream()
            for b in blocks:
                sess.feed(b)
            res = sess.finish()
            if sp is not None:
                sp.attrs.update(docs=1, residues=res.n_symbols,
                                blocks=sess.blocks, steps=sess.blocks,
                                h2d_bytes=moved[0], d2h_bytes=moved[1])
        return res

    def stream_docs(self, docs) -> ScanResult:
        """Scan many long documents block by block, side by side:
        :class:`ScanResult` (P, D), bit-identical to ``scan(docs)``.

        Step ``b`` sends block ``b`` of every document that has one as one
        ``(lanes, n_chunks * block_len)`` corpus, per group one round trip
        of the document path's executor (``lanes``: ``D`` rounded up to a
        power of two, so shards of any size share few programs). Each
        document's running states carry across steps; a document out of
        full blocks keeps its states. The ragged tails then advance
        sequentially, batched by tail length. Memory holds one block per
        lane. One ``scanner.stream`` span (``docs``, ``residues``,
        ``blocks``: full blocks of all documents, ``steps``, bytes) holds
        the round trips and tails."""
        pol = self.plan.chunking
        n_chunks = pol.n_chunks
        S = n_chunks * pol.block_len
        spec_stats: SpeculationStats | None = None
        with obs.span("scanner.stream") as sp, _scan_totals() as moved:
            with obs.span("scanner.encode"):
                enc = self._encode_docs(docs)
            D = len(enc)
            lanes = 1 << max(D - 1, 0).bit_length()
            n_blocks = np.array([len(e) // S for e in enc], dtype=np.int64)
            steps = int(n_blocks.max()) if D else 0
            states = [np.repeat(g.bank.starts.astype(np.int32)[:, None],
                                lanes, axis=1) for g in self.groups]
            for b in range(steps):
                live = np.flatnonzero(n_blocks > b)
                block = np.zeros((lanes, S), dtype=np.int32)
                for d in live:
                    block[d] = enc[d][b * S:(b + 1) * S]
                for gi, g in enumerate(self.groups):
                    if g.mode == "speculative":
                        new, st = self._speculative_finals(
                            g, self._speculation_profile(g, block), block,
                            states[gi], stream=True)
                        _count_speculation(st)
                        spec_stats = st if spec_stats is None \
                            else spec_stats.merged(st)
                    else:
                        bm = self._head_mappings(g, block, n_chunks,
                                                 stream=True)  # (Pg, lanes, n)
                        new = np.take_along_axis(
                            bm, states[gi][:, :, None].astype(np.int64),
                            axis=2)[:, :, 0]
                    states[gi][:, live] = new[:, live]
            tails: dict = {}
            for d, e in enumerate(enc):
                if len(e) > n_blocks[d] * S:
                    tails.setdefault(len(e) - int(n_blocks[d]) * S,
                                     []).append(d)
            for T, ds in tails.items():
                with obs.span("scanner.tail", docs=len(ds), symbols=T):
                    sym = np.stack([enc[d][len(enc[d]) - T:] for d in ds])
                    for gi, g in enumerate(self.groups):
                        states[gi][:, ds] = X.advance_states_sequential(
                            g.bank.tables, states[gi][:, ds], sym)
            hits = np.zeros((self.n_patterns, D), dtype=bool)
            for gi, g in enumerate(self.groups):
                hits[g.indices] = np.take_along_axis(
                    g.bank.accepting, states[gi][:, :D].astype(np.int64),
                    axis=1)
            residues = sum(len(e) for e in enc)
            obs.counter("engine.stream_blocks").inc(int(n_blocks.sum()))
            obs.counter("engine.stream_residues").inc(residues)
            if sp is not None:
                sp.attrs.update(docs=D, residues=residues,
                                blocks=int(n_blocks.sum()), steps=steps,
                                h2d_bytes=moved[0], d2h_bytes=moved[1])
        self.last_speculation = spec_stats or self.last_speculation
        return ScanResult(hits=hits, ids=self.ids, speculation=spec_stats)

    # -- introspection ------------------------------------------------------

    def describe(self) -> str:
        r = self.construction_report
        lines = [
            f"Scanner: {self.n_patterns} pattern(s), alphabet |Σ|="
            f"{len(self.alphabet)}, plan=({self.plan.mode}/"
            f"{self.plan.backend}/{self.plan.distribution}, "
            f"n_chunks={self.plan.chunking.n_chunks})",
            f"  construction: {r.rounds} round(s) via {r.method}, "
            f"cache {r.cache_hits} hit(s) / {r.cache_misses} miss(es), "
            f"{r.constructed} built, {r.blown} blown",
        ]
        for g in self.groups:
            extra = ""
            if g.mode == "sfa":
                extra = f", S_max={int(g.deltas.shape[1])}"
            elif g.mode == "speculative":
                extra = (f", m={self.plan.speculation.m}, "
                         f"source={self.plan.speculation.profile_source!r}"
                         if isinstance(self.plan.speculation.profile_source,
                                       str)
                         else f", m={self.plan.speculation.m}, "
                              f"source=explicit")
            lines.append(
                f"  group[{g.mode}]: {len(g.indices)} pattern(s), "
                f"n_max={g.n}{extra}"
            )
        s = self.last_speculation
        if s is not None:
            lines.append(
                f"  speculation: hit rate {s.hit_rate:.3f} "
                f"({s.hit_chunks}/{s.total_chunks} chunks), "
                f"{s.repaired_chunks} repaired in {s.repair_rounds} "
                f"round(s), {s.fallback_lanes} fallback lane(s)"
            )
        if self.last_trace_id is not None:
            summ = obs.trace_summary(self.last_trace_id)
            if summ["spans"]:
                lines.append(
                    f"  last trace {summ['trace_id']}: "
                    f"{len(summ['spans'])} span(s), "
                    f"wall {summ['wall_s'] * 1e3:.2f} ms"
                )
                for sp in summ["spans"][:8]:
                    lines.append(
                        f"    {sp['name']}: {sp['wall_s'] * 1e3:.2f} ms "
                        f"{sp['attrs'] or ''}".rstrip()
                    )
        return "\n".join(lines)


def _reference_doc_mappings(tables: np.ndarray, corpus: np.ndarray) -> np.ndarray:
    """Pure-NumPy oracle: compose each doc's transition function symbol by
    symbol over all states at once. (Pg, n, k), (D, L) -> (Pg, D, n)."""
    Pg, n, _ = tables.shape
    D, _ = corpus.shape
    out = np.empty((Pg, D, n), dtype=np.int32)
    ident = np.broadcast_to(np.arange(n, dtype=np.int32), (Pg, n))
    for d in range(D):
        out[:, d] = X.compose_sequential(tables, ident, corpus[d])
    return out
