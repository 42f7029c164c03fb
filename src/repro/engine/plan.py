"""Execution plans: the declarative half of the ``Scanner`` engine API.

A :class:`ScanPlan` says *how* to run a scan — matching mode, backend,
distribution, and chunking — while the :class:`~repro.engine.Scanner` facade
says *what* to scan. Splitting the two keeps every matching configuration the
repo supports (DFA vs SFA mode, single pattern vs bank, one device vs a mesh,
XLA vs Pallas inner loops) behind one entry point, which is the paper's own
framing: chunk transition functions combined by one associative monoid serve
them all.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, replace
from typing import Any

MODES = ("auto", "sfa", "enumeration", "speculative")
BACKENDS = ("reference", "xla", "pallas")
SPECULATION_SOURCES = ("sample", "store")
DISTRIBUTIONS = ("local", "shard_map")
CONSTRUCTION_METHODS = ("auto", "batched", "loop")
CONSTRUCTION_ENGINES = ("vectorized", "sequential", "jax")
CONSTRUCTION_FP_BACKENDS = ("auto", "xla", "pallas")
CONSTRUCTION_EXPAND_BACKENDS = ("auto", "xla", "pallas")
CONSTRUCTION_BUCKETINGS = ("auto", "size", "off")

#: Default SFA state budget for ``mode="auto"``: patterns whose exact SFA
#: closes within this many states get the paper's single-lookup inner loop;
#: the rest fall back to enumeration (Mytkowicz-style n-wide gathers). 512
#: splits the bundled PROSITE bank into a representative mix of both.
DEFAULT_SFA_STATE_BUDGET = 512


@dataclass(frozen=True)
class ChunkPolicy:
    """How inputs are cut into the paper's parallel chunks.

    ``n_chunks``
        chunk-level parallelism per document (and per stream block) — the
        paper's thread count.
    ``block_len``
        symbols per chunk in the streaming path; one stream block is a fixed
        ``(n_chunks, block_len)`` array, so every block reuses one compiled
        program (and one VMEM-resident table in the Pallas inner loop).
    ``bucket`` / ``bucket_edges``
        size-bucketing of the pattern bank: patterns are grouped so no
        pattern pays gathers more than ~2x wider than its own automaton
        (``core.multipattern.bucket_by_size``'s padding argument).
    """

    n_chunks: int = 8
    block_len: int = 256
    bucket: bool = False
    bucket_edges: tuple = (8, 16, 32, 64, 128, 256, 1024)

    def validate(self) -> "ChunkPolicy":
        if self.n_chunks < 1:
            raise ValueError(f"n_chunks must be >= 1, got {self.n_chunks}")
        if self.block_len < 1:
            raise ValueError(f"block_len must be >= 1, got {self.block_len}")
        if self.bucket and not self.bucket_edges:
            raise ValueError("bucket=True requires non-empty bucket_edges")
        return self


@dataclass(frozen=True)
class ConstructionPolicy:
    """How ``Scanner.compile`` builds the SFAs its plan needs.

    ``method``
        ``"batched"`` constructs every cache-missing pattern in one
        :func:`repro.construction.construct_bank` call (all frontiers advance
        simultaneously in jitted bulk-synchronous rounds — the paper's
        task-level construction parallelism); ``"loop"`` is the per-pattern
        sequential loop (``engine=`` picks the single-pattern engine);
        ``"auto"`` batches when at least 4 patterns miss the cache and loops
        otherwise (a bank round has to amortize its XLA compilation).
    ``cache``
        ``"shared"`` (the process-wide content-addressed
        :class:`repro.construction.SFACache` — recompiling the same patterns
        performs zero construction rounds), ``"off"``, or an explicit
        :class:`~repro.construction.SFACache` instance (isolated caches for
        tests and multi-tenant serving).
    ``store``
        optional persistent tier under the cache: a directory path (wrapped
        in :class:`repro.scanservice.ArtifactStore`) or any object speaking
        the backing protocol. Attached to the resolved cache, so SFAs
        persist across processes — a fresh process compiling previously-seen
        patterns performs zero construction rounds. Ignored when
        ``cache="off"``.
    ``distribution``
        ``"shard_map"`` shards the *pattern* axis of the batched construction
        buffers over ``mesh`` (default: a fresh 1-axis mesh named
        ``pattern_axis``); ``"local"`` keeps construction on one device.
    ``tile`` / ``max_retries``
        frontier states processed per pattern per round, and the per-pattern
        polynomial retry budget on a detected fingerprint collision.
    ``fingerprint_backend``
        the batched round's fingerprint stage: ``"xla"`` (fused clmul fold),
        ``"pallas"`` (the ``kernels.ops.fingerprint_bank`` Rabin kernel —
        bit-identical), or ``"auto"`` (pallas on a TPU runtime, xla
        elsewhere).
    ``expand_backend``
        the batched round's frontier-expansion stage: ``"xla"`` (fused
        ``jnp.take`` gather), ``"pallas"`` (the
        ``kernels.ops.expand_frontier_bank`` one-hot MXU gather —
        bit-identical), or ``"auto"`` (pallas on a TPU runtime, xla
        elsewhere).
    ``bucketing``
        size-bucketed construction banks: ``"size"`` partitions a batched
        bank by DFA state count so small patterns stop paying the widest
        pattern's frontier rows and sort lengths (the P=64 lever),
        ``"off"`` keeps one padded bank, ``"auto"`` buckets only when the
        bank is big and skewed enough to pay. Bit-identical either way.
    ``bucket_growth``
        active-set bucket shrink factor of the construction shape schedule
        (``repro.construction.round_schedule``): larger compiles fewer round
        shapes at the cost of more padding in mid-size rounds.
    """

    method: str = "auto"
    engine: str = "vectorized"
    tile: int = 128
    cache: Any = "shared"
    store: Any = None
    distribution: str = "local"
    mesh: Any = None
    pattern_axis: str = "pattern"
    max_retries: int = 4
    fingerprint_backend: str = "auto"
    expand_backend: str = "auto"
    bucketing: str = "auto"
    bucket_growth: int = 4

    def validate(self) -> "ConstructionPolicy":
        if self.method not in CONSTRUCTION_METHODS:
            raise ValueError(
                f"construction method must be one of {CONSTRUCTION_METHODS}, "
                f"got {self.method!r}"
            )
        if self.engine not in CONSTRUCTION_ENGINES:
            raise ValueError(
                f"construction engine must be one of {CONSTRUCTION_ENGINES}, "
                f"got {self.engine!r}"
            )
        if self.tile < 1:
            raise ValueError(f"construction tile must be >= 1, got {self.tile}")
        if self.max_retries < 1:
            raise ValueError("construction max_retries must be >= 1")
        if self.distribution not in DISTRIBUTIONS:
            raise ValueError(
                f"construction distribution must be one of {DISTRIBUTIONS}, "
                f"got {self.distribution!r}"
            )
        if self.fingerprint_backend not in CONSTRUCTION_FP_BACKENDS:
            raise ValueError(
                "construction fingerprint_backend must be one of "
                f"{CONSTRUCTION_FP_BACKENDS}, got {self.fingerprint_backend!r}"
            )
        if self.expand_backend not in CONSTRUCTION_EXPAND_BACKENDS:
            raise ValueError(
                "construction expand_backend must be one of "
                f"{CONSTRUCTION_EXPAND_BACKENDS}, got {self.expand_backend!r}"
            )
        if self.bucketing not in CONSTRUCTION_BUCKETINGS:
            raise ValueError(
                "construction bucketing must be one of "
                f"{CONSTRUCTION_BUCKETINGS}, got {self.bucketing!r}"
            )
        if self.bucket_growth < 2:
            raise ValueError(
                f"construction bucket_growth must be >= 2, "
                f"got {self.bucket_growth}"
            )
        from ..construction import SFACache

        if not (self.cache in ("shared", "off", None)
                or isinstance(self.cache, SFACache)):
            raise ValueError(
                "construction cache must be 'shared', 'off', None, or an "
                f"SFACache instance, got {self.cache!r}"
            )
        if not (self.store is None
                or isinstance(self.store, (str, os.PathLike))
                or (hasattr(self.store, "get")
                    and hasattr(self.store, "put_sfa"))):
            raise ValueError(
                "construction store must be None, a directory path, or an "
                "object with the ArtifactStore backing protocol "
                f"(get/put_sfa/put_blowup), got {self.store!r}"
            )
        return self

    def resolve_store(self):
        """-> the backing store object, or None. Paths wrap lazily in an
        :class:`repro.scanservice.ArtifactStore`."""
        if self.store is None:
            return None
        if isinstance(self.store, (str, os.PathLike)):
            from ..scanservice.store import ArtifactStore

            return ArtifactStore(self.store)
        return self.store

    def resolve_cache(self):
        """-> the SFACache to consult (with any configured backing store
        attached), or None when caching is off."""
        from ..construction import SFACache, shared_cache

        cache = None
        if isinstance(self.cache, SFACache):
            cache = self.cache
        elif self.cache == "shared":
            cache = shared_cache()
        if cache is not None:
            cache.attach_backing(self.resolve_store())
        return cache

    def with_(self, **overrides) -> "ConstructionPolicy":
        return replace(self, **overrides).validate()


@dataclass(frozen=True)
class SpeculationPolicy:
    """How ``mode="speculative"`` (and auto's speculative tier) speculates.

    ``m``
        speculated boundary states per pattern — every chunk runs from all
        ``m`` at once (a stacked ``(m, chunks)`` state axis), so cost scales
        with ``m`` where enumeration scales with the automaton's ``n``.
    ``sample_frac`` / ``max_sample``
        how much of the input the hot-state profiler reads when the profile
        comes from sampling: ``min(max_sample, sample_frac · corpus_size)``
        symbols off the corpus prefix.
    ``max_repair_rounds``
        convergence bound of the executor's validate/repair loop. Each round
        re-scans exactly one chunk per broken (pattern, doc) lane from its
        now-known entry state; lanes still unresolved at the bound fall back
        to full enumeration — results stay bit-identical either way, the
        bound only caps how long the cheap path keeps trying.
    ``profile_source``
        ``"sample"`` (profile the first scanned input, memoized per
        scanner — the profile is advisory, so reuse costs repairs at
        worst, never correctness),
        ``"store"`` (look up a persisted profile in the plan's
        ``construction.store`` by the pattern's ``dfa_cache_key``, sampling
        and persisting on a miss — the scan-service path), a mapping
        ``{pattern id: state sequence}``, or one explicit state sequence
        applied to every pattern (the adversarial-testing hook).
    ``auto_states``
        the ``auto``-mode tier threshold: a pattern whose SFA blows the
        state budget routes to speculation only when its DFA has at least
        this many states; smaller blowup patterns keep the enumeration
        fallback (their n-wide gathers are already cheap). 128 is a
        conservative bound on the measured crossover
        (``BENCH_speculative.json``): warm repeat scans win well below it,
        but a first scan also pays the sequential profiling pass.
    """

    m: int = 8
    sample_frac: float = 0.05
    max_sample: int = 4096
    max_repair_rounds: int = 8
    profile_source: Any = "sample"
    auto_states: int = 128

    def validate(self) -> "SpeculationPolicy":
        if self.m < 1:
            raise ValueError(f"speculation m must be >= 1, got {self.m}")
        if not (0.0 < self.sample_frac <= 1.0):
            raise ValueError(
                f"speculation sample_frac must be in (0, 1], "
                f"got {self.sample_frac}"
            )
        if self.max_sample < 1:
            raise ValueError("speculation max_sample must be >= 1")
        if self.max_repair_rounds < 1:
            raise ValueError("speculation max_repair_rounds must be >= 1")
        if self.auto_states < 1:
            raise ValueError("speculation auto_states must be >= 1")
        src = self.profile_source
        if isinstance(src, str):
            if src not in SPECULATION_SOURCES:
                raise ValueError(
                    f"speculation profile_source must be one of "
                    f"{SPECULATION_SOURCES}, a mapping, or a state sequence; "
                    f"got {src!r}"
                )
        elif not (hasattr(src, "keys") or hasattr(src, "__len__")
                  or hasattr(src, "__iter__")):
            raise ValueError(
                "speculation profile_source must be 'sample', 'store', a "
                f"mapping, or a state sequence, got {src!r}"
            )
        return self

    def with_(self, **overrides) -> "SpeculationPolicy":
        return replace(self, **overrides).validate()


@dataclass(frozen=True)
class ScanPlan:
    """One execution plan for a compiled :class:`~repro.engine.Scanner`.

    ``mode``
        ``"sfa"`` forces the paper's SFA matching (construction must fit the
        budget for *every* pattern, else ``StateBlowup`` propagates);
        ``"enumeration"`` forces the related-work all-states gather mode;
        ``"speculative"`` forces the hot-state speculation executor
        (:mod:`repro.speculative` — m speculated boundary states per chunk,
        validate + repair, bit-identical to enumeration by construction);
        ``"auto"`` attempts SFA construction per pattern under
        ``sfa_state_budget`` and, on ``StateBlowup``, falls back to
        speculation when the DFA has at least ``speculation.auto_states``
        states and to enumeration otherwise — the three-tier criterion.
    ``backend``
        ``"reference"`` (pure NumPy oracle), ``"xla"`` (jitted vmapped
        chunk matchers), or ``"pallas"`` (the ``match_bank_chunks_pallas``
        inner loop with VMEM-resident transposed tables). All three produce
        bit-identical results; they differ only in execution strategy.
    ``distribution``
        ``"local"`` or ``"shard_map"`` (documents shard over ``data_axis``
        of ``mesh``; a mesh over all devices is built when ``mesh`` is
        None, as construction's is).
    ``chunking``
        a :class:`ChunkPolicy`.
    ``construction``
        a :class:`ConstructionPolicy`: how the SFAs behind ``mode="sfa"`` /
        ``"auto"`` get built (batched bank rounds vs per-pattern loop,
        content-addressed caching, pattern-sharded construction meshes).
    ``speculation``
        a :class:`SpeculationPolicy`: the speculative tier's knobs (state
        count ``m``, profile sampling, repair bound, auto threshold).
    """

    mode: str = "auto"
    backend: str = "xla"
    distribution: str = "local"
    chunking: ChunkPolicy = field(default_factory=ChunkPolicy)
    construction: ConstructionPolicy = field(default_factory=ConstructionPolicy)
    speculation: SpeculationPolicy = field(default_factory=SpeculationPolicy)
    sfa_state_budget: int = DEFAULT_SFA_STATE_BUDGET
    mesh: Any = None
    data_axis: str = "data"

    def validate(self) -> "ScanPlan":
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {self.backend!r}"
            )
        if self.distribution not in DISTRIBUTIONS:
            raise ValueError(
                f"distribution must be one of {DISTRIBUTIONS}, "
                f"got {self.distribution!r}"
            )
        if self.sfa_state_budget < 1:
            raise ValueError("sfa_state_budget must be >= 1")
        if self.distribution == "shard_map" and self.backend != "xla":
            raise ValueError(
                "distribution='shard_map' currently requires backend='xla' "
                "(the reference backend has no mesh story and the Pallas "
                "inner loop is local-only for now)"
            )
        self.chunking.validate()
        self.construction.validate()
        self.speculation.validate()
        return self

    def with_(self, **overrides) -> "ScanPlan":
        """Functional update (``dataclasses.replace`` with validation)."""
        return replace(self, **overrides).validate()
