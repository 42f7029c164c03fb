"""Seeded inputs: the same seed gives the same inputs, every seed the same
sizes, and user patterns follow the variant rule."""

import numpy as np
import pytest

from bench import data, spec
from bench.tests.cells import files

CFG = files("prosite23-web", "poisson")["config"]


def test_length_classes_follow_swissprot():
    cls = data.length_classes(CFG["proteins"])
    assert cls["lengths"] == [34, 65, 125, 236, 444, 837, 1590, 2978]
    assert abs(sum(cls["shares"]) - 1) < 1e-9
    assert 355 < cls["mean"] < 365
    assert 0 < cls["removed_entries"] < 1e-4
    assert 0 < cls["removed_residues"] < 1e-3


def test_allocate_and_interleave():
    cls = data.length_classes(CFG["proteins"])
    counts = data.allocate(cls["shares"], 64)
    assert counts == [0, 1, 10, 24, 21, 7, 1, 0]
    order = data.interleave(counts)
    assert [order.count(c) for c in range(8)] == counts
    assert len(set(order[:8])) >= 3     # classes interleave, not sorted


def test_residues_in_bulk_follow_the_composition():
    rng = np.random.default_rng(7)
    docs = data.residues(rng, 200, 500, data.composition(CFG))
    assert len(docs) == 200 and {len(d) for d in docs} == {500}
    text = "".join(docs)
    assert set(text) <= set(data.ALPHABET)
    assert abs(text.count("L") / len(text) - 0.0966) < 0.005


@pytest.mark.parametrize("cell", ["prosite23-web.poisson",
                                  "prosite23-web.cold-patterns"])
def test_requests_deterministic_per_seed(cell):
    c = files(*cell.split(".", 1))

    def gen(seed):
        return spec.generator(c["traffic"]["generator"]).requests(
            c["config"], c["traffic"], seed, 5.0,
            set(c["config"]["bank"].values()))

    a, b, other = gen(2**33 + 5), gen(2**33 + 5), gen(2**33 + 6)
    assert [(r["due"], r["docs"], r["patterns"]) for r in a] == \
        [(r["due"], r["docs"], r["patterns"]) for r in b]
    assert [r["docs"] for r in a] != [r["docs"] for r in other]
    # The same work for every seed, in another order.
    assert sorted(sum(map(len, r["docs"])) for r in a) == \
        sorted(sum(map(len, r["docs"])) for r in other)


def test_cold_variants_are_fresh_and_keep_anchors():
    c = files("prosite23-web", "cold-patterns")
    seen = set(c["config"]["bank"].values())
    reqs = spec.generator("open_loop").requests(c["config"], c["traffic"],
                                                99, 120.0, seen)
    pats = [r["patterns"][0] for r in reqs]
    assert len(set(pats)) == len(pats)
    assert not set(pats) & set(c["config"]["bank"].values())
    for p in pats:
        assert p.endswith(">") == p.replace("x", "").endswith(">")


@pytest.mark.parametrize("source,kind", [
    ("[ST]-x(2)-[DE]", "spacer"), ("R-G-D", "residue"),
    ("[KRHQSA]-[DENQ]-E-L>", "residue"), ("[RK](2)-x-[ST]", "spacer")])
def test_variant_rule(source, kind):
    for seed in range(20):
        v = data.variant(source, kind, np.random.default_rng(seed))
        assert v != source
        assert v.endswith(">") == source.endswith(">")
        assert v.count("-") == source.count("-")
        if kind == "spacer":
            assert "x(0)" not in v


def test_variant_without_a_site_is_none():
    rng = np.random.default_rng(0)
    assert data.variant("[STAGCN]-[RKH]-[LIVMAFY]>", "spacer", rng) is None
    assert data.variant("[STAGCN]-[RKH]-[LIVMAFY]>", "residue", rng) is None


def test_bursts_and_subsets_follow_the_mix():
    c = files("prosite23-web", "poisson")
    traffic = dict(c["traffic"], rate_per_s=5.0,
                   bursts={"every_s": 10.0, "length_s": 2.0, "factor": 4.0},
                   patterns={"kind": "subset", "sizes": [1, 5], "zipf_s": 1.1})
    gen = spec.generator("open_loop")
    reqs = gen.requests(c["config"], traffic, 2**40 + 3, 200.0, set())
    due = np.array([r["due"] for r in reqs if r["due"] < 200.0])
    inside = ((due % 10.0) < 2.0).sum()
    # 2 s at 20/s against 8 s at 5/s in every 10 s: about half inside.
    assert 0.4 < inside / len(due) < 0.6
    assert 1_500 < len(due) < 2_100
    sizes = [len(r["patterns"]) for r in reqs]
    assert min(sizes) == 1 and max(sizes) == 5
    bank = set(c["config"]["bank"].values())
    assert all(set(r["patterns"]) <= bank for r in reqs)
    other = gen.requests(c["config"], traffic, 2**40 + 4, 200.0, set())
    assert sorted(map(len, (r["patterns"] for r in reqs))) == \
        sorted(map(len, (r["patterns"] for r in other)))


def test_release_order_mixes_raw_lengths():
    c = spec.cell("prosite23-batch.swissprot")
    traffic = dict(c["traffic"], order="release", template_seed=1,
                   lengths={"mode": "raw", "range": [8192, 35213]})
    gen = spec.generator("shards")
    docs = gen.cycle_docs(c["config"], traffic, 8, 7, 0)
    lengths = [len(d) for d in docs]
    assert len(docs) == 8 * traffic["cycle_shards"]
    assert min(lengths) >= 8192 and max(lengths) <= 35213
    assert len(set(lengths[:8])) > 4          # a shard mixes lengths
    again = gen.cycle_docs(c["config"], traffic, 8, 8, 0)
    assert sorted(lengths) == sorted(len(d) for d in again)
