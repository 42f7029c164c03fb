"""The ragged tail: the symbols past the largest prefix ``n_chunks`` divides.

``scan`` reads the head's final states and advances them through the tail
of all documents of a length group at once; ``mapping`` and
``census_windows`` compose whole mappings the same way. Every remainder of
the length modulo ``n_chunks`` gives the plain DFA's answer, in every mode
and on the xla and reference backends, and the tail's executor calls are
one per (length, group), never one per document.
"""

import numpy as np
import pytest

from repro.core.dfa import random_dfa
from repro.engine import ChunkPolicy, ScanPlan, Scanner
from repro.engine import executors as X

K = 4
N_CHUNKS = 8
DFAS = [random_dfa(n, K, seed=90 + n, n_accepting=1 + n // 2)
        for n in (3, 4, 5)]


@pytest.fixture(scope="module")
def scanner():
    """``scanner(mode, backend)``: one compiled scanner per plan."""
    made: dict = {}

    def get(mode: str, backend: str = "xla") -> Scanner:
        if (mode, backend) not in made:
            plan = ScanPlan(mode=mode, backend=backend,
                            sfa_state_budget=10_000,
                            chunking=ChunkPolicy(n_chunks=N_CHUNKS))
            made[mode, backend] = Scanner.compile(DFAS, plan)
        return made[mode, backend]

    return get


def _docs(lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, K, size=L).astype(np.int32) for L in lengths]


def _want_hits(docs) -> np.ndarray:
    return np.array([[bool(d.accepting[d.run(doc)]) for doc in docs]
                     for d in DFAS])


#: Corpora by case: lengths ``8m + r`` for every remainder ``r`` (two
#: length groups each), lengths below ``n_chunks`` (no head) with an empty
#: document among them, a single document, and an empty document alone.
CASES = {
    **{f"r{r}": [L for L in (N_CHUNKS + r, 2 * N_CHUNKS + r)
                 for _ in range(3)] for r in range(N_CHUNKS)},
    "no_head": list(range(N_CHUNKS)),
    "single": [3 * N_CHUNKS + 5],
    "empty": [0],
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("backend", ["xla", "reference"])
@pytest.mark.parametrize("mode", ["sfa", "enumeration", "speculative"])
def test_scan_equals_the_dfa_for_every_remainder(scanner, mode, backend,
                                                 case):
    sc = scanner(mode, backend)
    assert {g.mode for g in sc.groups} == {mode}
    docs = _docs(CASES[case], seed=len(case) * 31 + sum(CASES[case]))
    assert np.array_equal(sc.scan(docs).hits, _want_hits(docs))


def test_the_corpora_have_hits_and_misses():
    docs = [d for lengths in CASES.values()
            for d in _docs(lengths, seed=sum(lengths))]
    want = _want_hits(docs)
    assert want.any() and not want.all()


@pytest.mark.parametrize("r", [1, 4, 7])
@pytest.mark.parametrize("mode", ["sfa", "enumeration"])
def test_group_mappings_tail_equals_the_per_document_fold(scanner, mode, r):
    sc = scanner(mode)
    L = 2 * N_CHUNKS + r
    head_len = L - r
    corpus = np.stack(_docs([L] * 5, seed=100 + r))
    for g in sc.groups:
        head = sc._head_mappings(g, corpus[:, :head_len], N_CHUNKS)
        want = np.stack([X.compose_sequential(g.bank.tables, head[:, d],
                                              corpus[d, head_len:])
                         for d in range(len(corpus))], axis=1)
        got = sc._group_doc_mappings(g, corpus)
        assert got.shape == (len(g.indices), len(corpus), g.n)
        assert np.array_equal(got, want)


def _dfa_mapping(dfa, doc, n_max) -> np.ndarray:
    """Transition function of ``doc`` under ``dfa``, identity beyond its
    states: the scanner's padded layout."""
    out = np.arange(n_max, dtype=np.int32)
    out[:dfa.n_states] = [dfa.run(doc, state=q) for q in range(dfa.n_states)]
    return out


@pytest.mark.parametrize("L", [5, 2 * N_CHUNKS + 3])
@pytest.mark.parametrize("mode", ["sfa", "enumeration"])
def test_mapping_equals_the_dfa_over_a_ragged_input(scanner, mode, L):
    sc = scanner(mode)
    (doc,) = _docs([L], seed=200 + L)
    want = np.stack([_dfa_mapping(d, doc, sc.n_max) for d in DFAS])
    assert np.array_equal(sc.mapping(doc), want)


@pytest.mark.parametrize("stride", [5, N_CHUNKS + 4])
@pytest.mark.parametrize("mode", ["sfa", "enumeration"])
def test_census_windows_over_strides_n_chunks_does_not_divide(scanner, mode,
                                                             stride):
    sc = scanner(mode)
    window = 3 * stride
    (seq,) = _docs([11 * stride + 2], seed=300 + stride)
    W = (len(seq) - window) // stride + 1
    windows = [seq[i * stride:i * stride + window] for i in range(W)]
    assert np.array_equal(sc.census_windows(seq, window, stride).hits,
                          _want_hits(windows))


@pytest.mark.parametrize("mode", ["sfa", "enumeration", "speculative"])
def test_tail_calls_do_not_grow_with_the_documents(scanner, monkeypatch,
                                                    mode):
    calls = {"advance": 0, "compose": 0}

    def counted(name, fn):
        def wrapper(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return wrapper

    monkeypatch.setattr(X, "advance_states_sequential",
                        counted("advance", X.advance_states_sequential))
    monkeypatch.setattr(X, "compose_sequential",
                        counted("compose", X.compose_sequential))
    sc = scanner(mode)
    lengths = (N_CHUNKS + 3, 2 * N_CHUNKS + 6)
    for n in (8, 64):
        calls.update(advance=0, compose=0)
        docs = _docs([lengths[i % 2] for i in range(n)], seed=n)
        assert np.array_equal(sc.scan(docs).hits, _want_hits(docs))
        assert calls == {"advance": len(lengths) * len(sc.groups),
                         "compose": 0}, n
