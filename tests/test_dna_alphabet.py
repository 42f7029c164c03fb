"""A DNA bank (k = 4) through the string compile path: REBASE sites in
PROSITE syntax over ``"ACGT"``, one row in each tier (EcoRI: SFA; BglI:
enumeration; BslI: speculative) and a strand pair (BsaI, BsaI.rc), against
a plain reference: ``re.search`` over the sites' IUPAC classes. The scan,
the stream (one document, or many side by side) and a corpus job whose
short threshold streams some documents give the reference's hits bit for
bit. Without an alphabet every compile is the amino-acid one it was: the
bundled signatures' cache keys are pinned.
"""

import re

import numpy as np
import pytest

from repro.construction import SFACache, dfa_cache_key
from repro.core.multipattern import PatternBank
from repro.core.prosite import (
    PROSITE_EXTRA,
    PROSITE_SAMPLES,
    PrositeSyntaxError,
    compile_prosite,
    translate,
)
from repro.engine import ConstructionPolicy, ScanPlan, Scanner
from repro.scanservice import CorpusJob, CorpusManifest, ScanService

DNA = "ACGT"
#: REBASE recognition sequences (IUPAC) and the bank rows written for them.
SITES = {"EcoRI": "GAATTC", "BglI": "GCCNNNNNGGC", "BslI": "CCNNNNNNNGG",
         "BsaI": "GGTCTC", "BsaI.rc": "GAGACC"}
BANK = {"EcoRI": "G-A-A-T-T-C", "BglI": "G-C-C-x(5)-G-G-C",
        "BslI": "C-C-x(7)-G-G", "BsaI": "G-G-T-C-T-C",
        "BsaI.rc": "G-A-G-A-C-C"}
TIERS = {"EcoRI": "sfa", "BglI": "enumeration", "BslI": "speculative",
         "BsaI": "sfa", "BsaI.rc": "sfa"}
IUPAC = {"A": "A", "C": "C", "G": "G", "T": "T", "N": "ACGT"}
#: Lengths around the 2,048-symbol stream block: some streams end in a
#: tail alone, some cross one or two blocks.
LENGTHS = (150, 333, 700, 1500, 2100, 4500, 5000, 701)
STREAM_THRESHOLD = 1000


def reference_hits(docs) -> np.ndarray:
    out = np.zeros((len(SITES), len(docs)), dtype=bool)
    for p, site in enumerate(SITES.values()):
        rx = re.compile("".join(f"[{IUPAC[c]}]" for c in site))
        for d, doc in enumerate(docs):
            out[p, d] = rx.search(doc) is not None
    return out


def dna_docs(seed: int, lengths=LENGTHS) -> list:
    """Seeded i.i.d. bases at 50.8% GC (E. coli K-12 MG1655)."""
    rng = np.random.default_rng(seed)
    p = np.array([24.6, 25.4, 25.4, 24.6]) / 100
    return ["".join(np.array(list(DNA))[rng.choice(4, size=L, p=p)])
            for L in lengths]


def _plan(cache):
    return ScanPlan(construction=ConstructionPolicy(cache=cache))


@pytest.fixture(scope="module")
def cache():
    return SFACache()


@pytest.fixture(scope="module")
def scanner(cache):
    return Scanner.compile(BANK, _plan(cache), alphabet=DNA)


def test_each_row_lands_in_its_tier_over_four_symbols(scanner):
    assert scanner.alphabet == DNA
    assert scanner.pattern_modes == TIERS
    for g in scanner.groups:
        assert g.bank.tables.shape[2] == 4
        assert g.tables.shape[2] == 4
        if g.mode == "sfa":
            assert g.deltas.shape[2] == 4
    bank = PatternBank.from_patterns(BANK, alphabet=DNA)
    assert bank.tables.shape[2] == 4 and bank.ids == tuple(BANK)


@pytest.mark.parametrize("seed", [0, 1])
def test_scan_equals_the_reference(scanner, seed):
    docs = dna_docs(seed)
    want = reference_hits(docs)
    assert want.any() and not want.all()
    assert np.array_equal(scanner.scan(docs).hits, want)


def test_stream_equals_the_reference(scanner):
    docs = dna_docs(2, lengths=(4500, 2100, 5000))
    want = reference_hits(docs)
    for d, doc in enumerate(docs):
        pieces = [doc[i:i + 777] for i in range(0, len(doc), 777)]
        assert np.array_equal(scanner.stream(pieces).accepted, want[:, d])


@pytest.mark.parametrize("seed", [5, 6])
def test_documents_streamed_side_by_side_equal_the_reference(scanner, seed):
    """Seven documents in eight lanes: 0 to 4 full blocks each, tails of
    every length class, one lane of padding."""
    docs = dna_docs(seed, lengths=(4500, 2100, 5000, 1500, 9000, 4096, 6200))
    want = reference_hits(docs)
    assert want.any() and not want.all()
    assert np.array_equal(scanner.stream_docs(docs).hits, want)


def test_corpus_job_streams_and_equals_the_reference(tmp_path, cache):
    docs = dna_docs(3) + dna_docs(4)
    job = CorpusJob(BANK, CorpusManifest.from_docs(docs, shard_docs=5),
                    tmp_path / "job", plan=_plan(cache),
                    stream_threshold=STREAM_THRESHOLD, alphabet=DNA)
    assert any(len(d) >= STREAM_THRESHOLD for d in docs)
    assert job.run().complete
    assert np.array_equal(job.aggregate().hits, reference_hits(docs))


def test_service_job_takes_the_alphabet(tmp_path):
    docs = dna_docs(5, lengths=(900, 1200))
    svc = ScanService(tmp_path / "store")
    try:
        job = svc.corpus_job(list(BANK.values()),
                             CorpusManifest.from_docs(docs, shard_docs=2),
                             tmp_path / "job", alphabet=DNA)
        assert job.scanner.alphabet == DNA
        assert job.run().complete
        assert np.array_equal(job.aggregate().hits, reference_hits(docs))
    finally:
        svc.close()


def test_a_work_directory_is_not_reused_across_alphabets(tmp_path, cache):
    """The same strings over another alphabet are other automata: the job
    digest tells them apart."""
    man = CorpusManifest.from_docs(["GAATTCAC", "CCGG"], shard_docs=2)
    CorpusJob(["G-A-A-T-T-C"], man, tmp_path / "w", plan=_plan(cache),
              alphabet=DNA)
    with pytest.raises(ValueError, match="different job"):
        CorpusJob(["G-A-A-T-T-C"], man, tmp_path / "w", plan=_plan(cache))


def test_a_symbol_outside_the_alphabet_is_refused():
    for bad in ("G-A-E-T", "G-[AE]-T", "G-{E}-T"):
        with pytest.raises(PrositeSyntaxError, match="'E'.*'ACGT'"):
            translate(bad, DNA)
        with pytest.raises(PrositeSyntaxError):
            compile_prosite(bad, alphabet=DNA)
    with pytest.raises(PrositeSyntaxError, match="'B'.*'ACDEFGHIKLMNPQRSTVWY'"):
        translate("[AB]")


def test_encode_names_the_symbol_and_the_alphabet(scanner):
    assert scanner.encode("ACGT").tolist() == [0, 1, 2, 3]
    with pytest.raises(ValueError, match="'N'.*'ACGT'"):
        scanner.encode("ACGNT")
    with pytest.raises(ValueError, match="'N'"):
        scanner.scan(["GAATTCN"])


def test_dfas_over_another_alphabet_are_refused(scanner):
    with pytest.raises(ValueError, match="one alphabet"):
        Scanner.compile([compile_prosite("R-G-D")], alphabet=DNA)


#: ``dfa_cache_key`` of each bundled signature compiled with no alphabet,
#: as the amino-acid-only compiler gave them before the alphabet parameter.
PINNED_KEYS = {
    "PS00001": "67576aaf23f71cccc65164aad4243eb11bb01e545302fea63793811d63b71c3d",
    "PS00004": "9faca0a22cae12de87318754c77d9f58c672bf76984a135f4900f792de862e36",
    "PS00005": "845faeb157f8b8d1dae277882854f5cf99d37080929748c719c949bc32ac1813",
    "PS00006": "8d8ffff7800055cbd043810749f207d145f7e8d4c49692e41b393bc54860c378",
    "PS00007": "d22f67dc08dd943bea4fc30646e054ec33001249f2dd3b60ca7feecf58e49afb",
    "PS00008": "24926a06ce51f19f654e4b913edd89ef3e7131eb74fcbf415afa71b41e55d1d0",
    "PS00009": "96e845766d7a03b6b59c82451906815e2b04613d691e482281a756f81576fb74",
    "PS00016": "d6b85f60e9890bfa5e34f2e45c18e3625ac981e68082638f81367d95289aa0ba",
    "PS00017": "6c00086cf241191d948d68045db518db0cb3beba371b33c5a3e5281b179b3ffe",
    "PS00002": "d65db482734910b3ee51c958ed15098650b7c5129d5204401ea8e18284d9ef67",
    "PS00010": "d5bc87553e09b7ce2bf98ee26201364bbde9613a6b6b0416e8a3a4c9ec3253e9",
    "PS00014": "b31dfa408530c184edc0af4df0bdba3a8bddcfef79b5a8fd0db9dca02835e0bf",
    "PS00342": "b2aca4cb5a13b8a6bf21e9737c8b3ac55ee44cdf3ea53b170f37df88e4ffd060",
    "SYN00001": "6573e4a81d3b76f2e186fa0af33831f76c57d779e031e4b4b9118345035ee0f7",
    "SYN00002": "58ae79a9c315efd90c3b0fadc7bb10296314ab0f7d2b3ab4e5be30541a215f11",
    "SYN00003": "7b27b9c27626c21931058f3aed5a2d308a69320a1324bd47ddf10c3314addddc",
    "SYN00004": "631dba9a76480fa86d86a619f8de98cf9926677268a7348779abcd354f32db16",
    "SYN00005": "91436c6ab0644643e78b3c949da7819d2d421c0e0d393f7cdf54141f108d53e9",
    "SYN00006": "d51d27b363877726697bce60cbcfafa3afe5ebbd5739f709a590bf2f335ba324",
    "SYN00007": "ba69d56e61fbbe44b57fe17b74dad70d2c4455ba96c897b74e5f948ef7344925",
    "SYN00008": "cb12f6a5416d705f834b26951bc391b5a7b2753bf154c7d67a8e30e998840f1c",
    "SYN00009": "3a271945e86196114bd8041bd5a722460f5b65269bb816bb316ba144f6c45b0c",
    "SYN00010": "8a536729f7c5a282e4f7a0ff4e0fad4a4fcd5f1d6c802fd57b53d089f5a7da1c",
}


def test_default_compile_keys_are_unchanged():
    pool = {**PROSITE_SAMPLES, **PROSITE_EXTRA}
    assert list(pool) == list(PINNED_KEYS)
    sc = Scanner.compile(list(pool.values()), ScanPlan(mode="enumeration"))
    assert [dfa_cache_key(d) for d in sc._dfas] == list(PINNED_KEYS.values())
    assert [dfa_cache_key(compile_prosite(p)) for p in pool.values()] == \
        list(PINNED_KEYS.values())
