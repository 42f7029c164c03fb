"""Speculative scanning: bit-identity under any speculation quality.

The subsystem's whole contract is that speculation moves *work*, never
*results*: a perfect hot-state profile settles every chunk in one pass, an
adversarial profile repairs every chunk (or falls back to enumeration), and
the hit matrix is identical either way. These tests pin that down on the
full bundled PROSITE bank, on random DFAs over ragged corpora, on inputs
engineered to force a 0% speculation hit rate, through streaming, and
through shard_map — plus the stats invariants and the profile persistence
path in the artifact store.
"""

import numpy as np
import pytest
from _strategies import given, settings, st

from repro.compat import make_mesh
from repro.core.dfa import DFA, random_dfa
from repro.core.prosite import PROSITE_EXTRA, PROSITE_SAMPLES, synthetic_protein
from repro.engine import ScanPlan, Scanner, SpeculationPolicy
from repro.scanservice import ArtifactStore, ScanService
from repro.speculative import (
    HotStateProfile,
    SpeculationStats,
    profile_hot_states,
    stack_profile_states,
)

ALL_BUNDLED = sorted({**PROSITE_SAMPLES, **PROSITE_EXTRA})


def _random_docs(seed, n_docs, length, k):
    rng = np.random.default_rng(seed)
    return rng.integers(0, k, size=(n_docs, length)).astype(np.int32)


def _two_state_dfa(n_states=6, k=4):
    """Only states {0, 1} are reachable (they alternate on every symbol);
    states 2..n-1 exist purely so a profile can speculate unreachable ones.
    """
    table = np.zeros((n_states, k), dtype=np.int32)
    table[0, :] = 1
    table[1, :] = 0
    for s in range(2, n_states):
        table[s, :] = s
    accepting = np.zeros(n_states, dtype=bool)
    accepting[1] = True
    return DFA(table=table, start=0, accepting=accepting, alphabet="abcd"[:k])


# --------------------------------------------------------------------------
# Policy validation
# --------------------------------------------------------------------------


def test_speculation_policy_validation():
    for bad in [
        dict(m=0),
        dict(sample_frac=0.0),
        dict(sample_frac=1.5),
        dict(max_sample=0),
        dict(max_repair_rounds=0),
        dict(auto_states=0),
        dict(profile_source="magic"),
        dict(profile_source=42),
    ]:
        with pytest.raises(ValueError):
            SpeculationPolicy(**bad).validate()
    assert ScanPlan(mode="speculative").validate().speculation.m == 8
    pol = SpeculationPolicy().with_(m=4, profile_source="store")
    assert (pol.m, pol.profile_source) == (4, "store")
    # explicit sources validate: sequences and mappings both pass
    SpeculationPolicy(profile_source=[0, 1, 2]).validate()
    SpeculationPolicy(profile_source={"p": [0]}).validate()


# --------------------------------------------------------------------------
# Bit-identity: bundled bank, random DFAs, forced misspeculation
# --------------------------------------------------------------------------


def test_bundled_bank_bit_identity():
    """The acceptance criterion: mode='speculative' == mode='enumeration'
    on the full bundled PROSITE bank."""
    docs = [synthetic_protein(60 + 17 * i, seed=i) for i in range(8)]
    sp = Scanner.compile(ALL_BUNDLED, ScanPlan(mode="speculative"))
    en = Scanner.compile(ALL_BUNDLED, ScanPlan(mode="enumeration"))
    rs, re = sp.scan(docs), en.scan(docs)
    assert np.array_equal(rs.hits, re.hits)
    st_ = rs.speculation
    assert isinstance(st_, SpeculationStats)
    assert st_.total_chunks > 0
    assert sp.last_speculation is st_
    assert "speculation" in sp.describe()


@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    n_states=st.integers(min_value=2, max_value=40),
    m=st.integers(min_value=1, max_value=6),
    sample_frac=st.floats(min_value=0.01, max_value=1.0),
)
def test_speculative_equals_enumeration_random(seed, n_states, m, sample_frac):
    """Property: random DFAs, ragged doc lengths (incl. sub-chunk and empty
    docs), any m / sample size -> identical hit matrices and stats sanity."""
    k = 5
    dfas = [random_dfa(n_states, k, seed=seed + j) for j in range(3)]
    rng = np.random.default_rng(seed)
    docs = [rng.integers(0, k, size=int(L)).astype(np.int32)
            for L in [0, 3, 17, 64, 64, 129]]
    plan = ScanPlan(
        mode="speculative",
        speculation=SpeculationPolicy(m=m, sample_frac=sample_frac),
    )
    rs = Scanner.compile(dfas, plan).scan(docs)
    re = Scanner.compile(dfas, ScanPlan(mode="enumeration")).scan(docs)
    assert np.array_equal(rs.hits, re.hits)
    s = rs.speculation
    assert s.repaired_chunks <= s.total_chunks
    assert 0.0 <= s.hit_rate <= 1.0
    if s.fallback_lanes == 0:
        # every chunk was settled by exactly one of the two cheap paths
        assert s.hit_chunks + s.repaired_chunks == s.total_chunks
    if s.hit_rate == 1.0:
        assert s.repair_rounds == 0


def test_forced_misspeculation_repairs_everything():
    """An unreachable-state profile forces a 0% hit rate: every chunk goes
    through the repair pass, and the result is still exact."""
    dfa = _two_state_dfa()
    docs = _random_docs(0, 4, 80, 4)  # 80 = 10 per chunk x 8 chunks
    plan = ScanPlan(
        mode="speculative",
        speculation=SpeculationPolicy(
            m=2, profile_source=np.asarray([2, 3]), max_repair_rounds=8
        ),
    )
    sp = Scanner.compile([dfa], plan)
    rs = sp.scan(docs)
    re = Scanner.compile([dfa], ScanPlan(mode="enumeration")).scan(docs)
    assert np.array_equal(rs.hits, re.hits)
    s = rs.speculation
    assert s.hit_chunks == 0 and s.hit_rate == 0.0
    assert s.fallback_lanes == 0
    assert s.repaired_chunks == s.total_chunks  # the repair-everything path
    assert s.repair_rounds == 8  # one chunk per lane per round, 8 chunks


def test_repair_bound_falls_back_to_enumeration():
    """With the repair budget too small to converge, unresolved lanes take
    the guaranteed enumeration fallback — results identical regardless."""
    dfa = _two_state_dfa()
    docs = _random_docs(1, 4, 80, 4)
    plan = ScanPlan(
        mode="speculative",
        speculation=SpeculationPolicy(
            m=2, profile_source=np.asarray([2, 3]), max_repair_rounds=1
        ),
    )
    rs = Scanner.compile([dfa], plan).scan(docs)
    re = Scanner.compile([dfa], ScanPlan(mode="enumeration")).scan(docs)
    assert np.array_equal(rs.hits, re.hits)
    assert rs.speculation.fallback_lanes > 0
    assert rs.speculation.repair_rounds == 1


def test_fallback_counts_its_enumeration_work():
    """The fallback's ``scanner.device`` span is an enumeration round trip:
    its ``pattern_residues`` is patterns x fallback lanes x head length,
    and ``engine.enum_pattern_residues`` moves by it."""
    from repro import obs

    dfa = _two_state_dfa()
    docs = _random_docs(1, 4, 80, 4)
    plan = ScanPlan(
        mode="speculative",
        speculation=SpeculationPolicy(
            m=2, profile_source=np.asarray([2, 3]), max_repair_rounds=1
        ),
    )
    sc = Scanner.compile([dfa], plan)
    obs.enable()
    before = obs.snapshot("engine")
    mark = max((s.span_id for s in obs.recent_spans(1 << 20)), default=0)
    rs = sc.scan(docs)
    moved = obs.snapshot_delta(before, obs.snapshot("engine"))
    (fb,) = [s for s in obs.recent_spans(1 << 20) if s.span_id > mark
             and s.name == "scanner.device"
             and s.attrs["mode"] == "enumeration"]
    assert rs.speculation.fallback_lanes > 0
    assert fb.attrs["pattern_residues"] == 1 * fb.attrs["docs"] * 80 > 0
    assert moved["engine.enum_pattern_residues"] == \
        fb.attrs["pattern_residues"]


def test_perfect_profile_hits_everything():
    """Speculating *all* states is a perfect profile: hit rate 1, zero
    repair rounds (the stats invariant's other edge)."""
    dfa = _two_state_dfa(n_states=4)
    docs = _random_docs(2, 3, 40, 4)
    plan = ScanPlan(
        mode="speculative",
        speculation=SpeculationPolicy(m=4, profile_source=np.arange(4)),
    )
    rs = Scanner.compile([dfa], plan).scan(docs)
    s = rs.speculation
    assert s.hit_rate == 1.0
    assert s.repair_rounds == 0
    assert s.repaired_chunks == 0 and s.fallback_lanes == 0


def test_explicit_profile_sources():
    dfa = _two_state_dfa()
    docs = _random_docs(3, 2, 40, 4)
    by_id = Scanner.compile(
        {"p": dfa},
        ScanPlan(mode="speculative",
                 speculation=SpeculationPolicy(m=2, profile_source={"p": [0, 1]})),
    ).scan(docs)
    re = Scanner.compile({"p": dfa}, ScanPlan(mode="enumeration")).scan(docs)
    assert np.array_equal(by_id.hits, re.hits)
    with pytest.raises(ValueError, match="missing pattern"):
        Scanner.compile(
            {"p": dfa},
            ScanPlan(mode="speculative",
                     speculation=SpeculationPolicy(profile_source={"q": [0]})),
        ).scan(docs)
    with pytest.raises(ValueError, match="non-empty"):
        Scanner.compile(
            {"p": dfa},
            ScanPlan(mode="speculative",
                     speculation=SpeculationPolicy(profile_source=[])),
        ).scan(docs)


# --------------------------------------------------------------------------
# Streaming and shard_map
# --------------------------------------------------------------------------


def test_stream_equals_scan_speculative():
    """stream() under speculation carries exact states across blocks: its
    accepts/finals equal the enumeration stream's (and scan's) bit for bit;
    the whole-input mapping is unavailable by design (None)."""
    patterns = ["PS00001", "PS00007", "PS00010"]
    text = synthetic_protein(7000, seed=42)
    pieces = [text[i:i + 1234] for i in range(0, len(text), 1234)]
    sp = Scanner.compile(patterns, ScanPlan(mode="speculative"))
    en = Scanner.compile(patterns, ScanPlan(mode="enumeration"))
    rs, re = sp.stream(pieces), en.stream(pieces)
    assert rs.mapping is None and re.mapping is not None
    assert np.array_equal(rs.final_states, re.final_states)
    assert np.array_equal(rs.accepted, re.accepted)
    assert isinstance(rs.speculation, SpeculationStats)
    assert rs.speculation.total_chunks > 0
    # and the stream equals a whole-corpus scan of the concatenation
    assert np.array_equal(rs.accepted, sp.scan([text]).hits[:, 0])


def test_misspeculated_stream_still_exact():
    """A block whose speculation misses entirely (profile of unreachable
    states, repair budget 0-ish) exercises the stream's per-block
    enumeration fallback."""
    dfa = _two_state_dfa()
    rng = np.random.default_rng(9)
    syms = rng.integers(0, 4, size=5000).astype(np.int32)
    plan = ScanPlan(
        mode="speculative",
        speculation=SpeculationPolicy(
            m=2, profile_source=np.asarray([2, 3]), max_repair_rounds=1
        ),
    )
    sp = Scanner.compile([dfa], plan)
    en = Scanner.compile([dfa], ScanPlan(mode="enumeration"))
    rs = sp.stream([syms[:2600], syms[2600:]])
    re = en.stream([syms[:2600], syms[2600:]])
    assert np.array_equal(rs.final_states, re.final_states)
    assert np.array_equal(rs.accepted, re.accepted)
    assert rs.speculation.fallback_lanes > 0


def test_shard_map_equals_local():
    mesh = make_mesh((1,), ("data",))
    plan = ScanPlan(mode="speculative")
    dist = plan.with_(distribution="shard_map", mesh=mesh)
    docs = _random_docs(5, 4, 96, 20)
    patterns = ["PS00007", "PS00010"]
    r_local = Scanner.compile(patterns, plan).scan(docs)
    r_dist = Scanner.compile(patterns, dist).scan(docs)
    assert np.array_equal(r_local.hits, r_dist.hits)
    assert r_dist.speculation.total_chunks == r_local.speculation.total_chunks


# --------------------------------------------------------------------------
# auto-mode tiering
# --------------------------------------------------------------------------


def test_auto_tier_routes_by_dfa_size():
    """auto's blowup tier: budget-blowing patterns go speculative iff their
    DFA has >= auto_states states; the bundled bank's blowup patterns are
    all smaller than the default threshold, so default plans are unchanged."""
    big = random_dfa(150, 20, seed=3)
    small = random_dfa(30, 20, seed=4)
    sc = Scanner.compile({"big": big, "small": small},
                         ScanPlan(mode="auto", sfa_state_budget=5))
    assert sc.pattern_modes["big"] == "speculative"
    assert sc.pattern_modes["small"] == "enumeration"
    # the threshold is the policy knob
    sc2 = Scanner.compile(
        {"big": big, "small": small},
        ScanPlan(mode="auto", sfa_state_budget=5,
                 speculation=SpeculationPolicy(auto_states=20)),
    )
    assert sc2.pattern_modes["small"] == "speculative"
    # default bundled bank: no speculative tier engaged
    default = Scanner.compile(ALL_BUNDLED, ScanPlan())
    assert "speculative" not in set(default.pattern_modes.values())
    # and the mixed auto scan stays exact
    docs = _random_docs(6, 3, 100, 20)
    r_auto = sc.scan(docs)
    r_enum = Scanner.compile({"big": big, "small": small},
                             ScanPlan(mode="enumeration")).scan(docs)
    assert np.array_equal(r_auto.hits, r_enum.hits)


# --------------------------------------------------------------------------
# Profiler unit behavior
# --------------------------------------------------------------------------


def test_profiler_top_m_and_stacking():
    dfa = _two_state_dfa(n_states=6)
    tables = dfa.table[None].astype(np.int32)
    sample = np.zeros(99, dtype=np.int32)  # alternates 0 -> 1 -> 0 -> ...
    [prof] = profile_hot_states(tables, np.asarray([0]), sample, m=3)
    # states 0 and 1 split all visits; unvisited states pad in id order
    assert set(prof.states[:2]) == {0, 1}
    assert prof.states[2] == 2
    assert prof.weights[0] >= prof.weights[1] > prof.weights[2] == 0.0
    assert prof.sample_len == 99
    # JSON round-trip and m-normalization (truncate / pad / clip)
    back = HotStateProfile.from_json(prof.to_json())
    assert np.array_equal(back.states, prof.states)
    stacked = stack_profile_states([back], m=5, n_max=4)
    assert stacked.shape == (1, 5)
    assert stacked.max() <= 3
    assert HotStateProfile.from_json({"garbage": 1}) is None


# --------------------------------------------------------------------------
# Profile persistence (store tier)
# --------------------------------------------------------------------------


def test_store_profile_roundtrip_and_isolation(tmp_path):
    store = ArtifactStore(tmp_path)
    prof = HotStateProfile(
        states=np.asarray([3, 1], dtype=np.int32),
        weights=np.asarray([0.7, 0.2]), sample_len=10,
    )
    store.put_profile("ab" + "0" * 62, prof.to_json())
    meta = store.get_profile("ab" + "0" * 62)
    assert meta is not None and meta["states"] == [3, 1]
    assert store.get_profile("cd" + "0" * 62) is None
    # profiles live outside the artifact namespace: nothing leaks into the
    # SFA walks, eviction, or len()
    assert len(store) == 0
    assert store.keys() == []
    assert list(store.entries()) == []
    assert store.profile_keys() == ["ab" + "0" * 62]
    # corrupt profile degrades to a miss
    store._profile_path("ab" + "0" * 62).write_text("{broken")
    assert store.get_profile("ab" + "0" * 62) is None


def test_store_backed_profile_source(tmp_path):
    """profile_source='store': first scan samples and persists, a fresh
    scanner reuses the persisted profile, results always exact."""
    from repro.engine import ConstructionPolicy

    dfa = random_dfa(40, 5, seed=11)
    docs = _random_docs(12, 3, 64, 5)
    plan = ScanPlan(
        mode="speculative",
        construction=ConstructionPolicy(store=tmp_path),
        speculation=SpeculationPolicy(profile_source="store"),
    )
    sc1 = Scanner.compile([dfa], plan)
    r1 = sc1.scan(docs)
    store = ArtifactStore(tmp_path)
    assert len(store.profile_keys()) == 1
    persisted = store.get_profile(store.profile_keys()[0])
    # a fresh scanner resolves the persisted profile (and memoizes it)
    sc2 = Scanner.compile([dfa], plan)
    r2 = sc2.scan(docs)
    g = next(g for g in sc2.groups if g.mode == "speculative")
    assert g._spec_profile is not None
    assert np.array_equal(
        g._spec_profile[0][: len(persisted["states"])],
        np.asarray(persisted["states"], dtype=np.int32),
    )
    re = Scanner.compile([dfa], ScanPlan(mode="enumeration")).scan(docs)
    assert np.array_equal(r1.hits, re.hits)
    assert np.array_equal(r2.hits, re.hits)


def test_service_upgrades_profile_source(tmp_path):
    with ScanService(store_dir=tmp_path) as svc:
        assert svc.plan.speculation.profile_source == "store"
    with ScanService() as svc:
        assert svc.plan.speculation.profile_source == "sample"
    # an explicit source is respected, store or not
    plan = ScanPlan(speculation=SpeculationPolicy(profile_source=[0, 1]))
    with ScanService(store_dir=tmp_path, plan=plan) as svc:
        assert list(svc.plan.speculation.profile_source) == [0, 1]


def test_scheduler_counts_speculative_patterns(tmp_path):
    """The service path end to end: an over-budget pattern routed to the
    speculative tier by auto mode is served and counted."""
    big = random_dfa(150, 20, seed=21)
    plan = ScanPlan(mode="auto", sfa_state_budget=5)
    with ScanService(store_dir=tmp_path, plan=plan) as svc:
        t = svc.submit([big, "PS00016"], ["ACDEFGHIKLMNPQRSTVWY" * 5])
        res = t.result()
        assert res.hits.shape == (2, 1)
        assert svc.scheduler.stats.speculative_patterns == 1
        ref = Scanner.compile(
            [big, "PS00016"], ScanPlan(mode="enumeration")
        ).scan(["ACDEFGHIKLMNPQRSTVWY" * 5])
        assert np.array_equal(res.hits, ref.hits)
