"""The plain reference agrees with the program's sequential oracle at a
tiny size, and its control (4-bit residues) does not."""

import numpy as np
import pytest

from bench import data, reference
from bench.tests.cells import files

BANK = files("prosite23-web", "poisson")["config"]["bank"]


@pytest.mark.parametrize("pattern,regex", [
    ("N-{P}-[ST]-{P}", "N[^\\nP][ST][^\\nP]"),
    ("[RK](2)-x-[ST]", "[RK]{2}[^\\n][ST]"),
    ("<M-x(2)-[KR]", "^M[^\\n]{2}[KR]"),
    ("[KRHQSA]-[DENQ]-E-L>", "[KRHQSA][DENQ]EL$"),
    ("C-x(2,4)-C", "C[^\\n]{2,4}C"),
])
def test_to_regex(pattern, regex):
    assert reference.to_regex(pattern) == regex


def test_reference_equals_census_sequential():
    from repro.core.multipattern import PatternBank
    from repro.core.multipattern import census_sequential
    from repro.engine import Scanner

    rng = np.random.default_rng(11)
    probs = np.full(20, 1 / 20)
    docs = data.residues(rng, 40, 97, probs) + data.residues(rng, 7, 5, probs)
    bank = PatternBank.from_patterns(BANK)
    sc = Scanner.compile(bank, backend="reference")
    enc = [sc.encode(d) for d in docs]
    want = reference.hits(list(BANK.values()), docs)
    by_len = {}
    for i, e in enumerate(enc):
        by_len.setdefault(len(e), []).append(i)
    for idx in by_len.values():
        counts = census_sequential(bank, np.stack([enc[i] for i in idx]))
        assert np.array_equal(counts, want[:, idx].sum(axis=1))
    assert np.array_equal(sc.scan(docs).hits, want)
    assert want.any() and not want.all()


def test_control_departs_from_the_reference():
    rng = np.random.default_rng(12)
    docs = data.residues(rng, 50, 300, np.full(20, 1 / 20))
    pats = list(BANK.values())
    good = reference.hits(pats, docs)
    ctl = reference.hits(pats, [reference.fold4(d) for d in docs])
    assert (good != ctl).sum() > 0
    assert len(set("".join(reference.fold4(d) for d in docs))) == 16


def test_documents_never_share_a_match():
    docs = ["AAR", "GDX"[:2] + "A", "RGD"]
    assert reference.hits(["R-G-D"], docs).tolist() == [[False, False, True]]
    assert reference.hits(["<R-G"], ["ARG", "RGA"]).tolist() == [[False, True]]
