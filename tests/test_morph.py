"""Pattern-morphing count algebra (``compiler.morph``): identity
correctness against brute force, store persistence/versioning, the
compile fast path and held-hom costing, end-to-end consumers, and the
morph-off bit-for-bit guarantee."""
import itertools
import json
import os

import numpy as np
import pytest

from repro import analysis, compiler, obs
from repro.compiler import costing, frontend
from repro.compiler import morph as morphlib
from repro.compiler.cache import PlanCache, graph_signature, plan_key
from repro.compiler.ir import pattern_key
from repro.core.counting import CountingEngine
from repro.core.pattern import Pattern, chain, clique, cycle
from repro.core.quotient import quotient_terms
from repro.graph import generators as gen
from repro.graph.storage import Graph


# -- brute-force oracles ----------------------------------------------------------
# memoised by (pattern key, graph signature): hypothesis examples reuse a
# handful of graphs and the same small quotients (K2, P3, ...) constantly

_BRUTE_MEMO: dict = {}


def _adj(g):
    adj = set()
    for u, v in map(tuple, g.edges):
        adj.add((u, v))
        adj.add((v, u))
    return adj


def _brute(kind, q, g, tuples):
    memo_key = (kind, pattern_key(q), graph_signature(g))
    if memo_key in _BRUTE_MEMO:
        return _BRUTE_MEMO[memo_key]
    adj = _adj(g)
    total = 0
    for f in tuples:
        if q.labels is not None and g.labels is not None and any(
                g.labels[f[v]] != q.labels[v] for v in range(q.n)):
            continue
        if all((f[u], f[v]) in adj for u, v in q.edges):
            total += 1
    _BRUTE_MEMO[memo_key] = total
    return total


def brute_hom(q, g):
    """hom(q, g) by enumeration (label-respecting when both carry labels)."""
    return _brute("hom", q, g,
                  itertools.product(range(g.n), repeat=q.n))


def brute_inj(p, g):
    """inj(p, g): injective homomorphisms by enumeration."""
    return _brute("inj", p, g,
                  itertools.permutations(range(g.n), p.n))


def warm_with_brute_homs(p, g, store):
    """Populate the store with brute-force homs of every quotient of p."""
    gsig = graph_signature(g)
    for _, q in quotient_terms(p.canonical()):
        store.put(gsig, "hom", q, brute_hom(q, g))
    return gsig


# -- pattern_key inversion --------------------------------------------------------

def test_pattern_key_roundtrip():
    pats = [chain(3), chain(5), cycle(4), cycle(5), clique(4),
            Pattern(4, [(0, 1), (1, 2), (2, 3), (0, 3), (0, 2)]),
            Pattern(3, [(0, 1), (1, 2)], labels=(1, 0, 1)),
            Pattern(4, [(0, 1), (1, 2), (2, 3)], labels=(0, 2, 0, 1))]
    for p in pats:
        pc = p.canonical()
        assert morphlib.pattern_from_key(pattern_key(p)) == pc


# -- golden identity locks --------------------------------------------------------

def test_golden_wedge_triangle_identity():
    """inj(wedge) = hom(wedge) - hom(K2); count(K3) = hom(K3) / 6."""
    wedge = chain(3)
    terms = quotient_terms(wedge.canonical())
    by_pattern = {q: c for c, q in terms}
    assert by_pattern == {wedge.canonical(): 1, clique(2).canonical(): -1}
    assert quotient_terms(clique(3)) == ((1, clique(3)),)

    g = gen.erdos_renyi(24, 4.0, seed=11)
    store = morphlib.CountStore()
    gsig = warm_with_brute_homs(wedge, g, store)
    cand = morphlib.derive(wedge, store, gsig)
    assert cand.complete
    assert cand.value * wedge.aut_order() == brute_inj(wedge, g)

    store2 = morphlib.CountStore()
    gsig2 = warm_with_brute_homs(clique(3), g, store2)
    tri = morphlib.derive(clique(3), store2, gsig2)
    assert tri.complete and tri.divisor == 6
    assert tri.value * 6 == brute_inj(clique(3), g)


def test_golden_4path_4cycle_identities():
    """Coefficient locks: inj(C4) = hom(C4) - 2 hom(P3) + hom(K2);
    inj(P4) = hom(P4) - 2 hom(P3) - hom(K3) + hom(K2)."""
    p3, c4, p4 = chain(3).canonical(), cycle(4).canonical(), \
        chain(4).canonical()
    c4_terms = {q: c for c, q in quotient_terms(c4)}
    assert c4_terms == {c4: 1, p3: -2, clique(2).canonical(): 1}
    p4_terms = {q: c for c, q in quotient_terms(p4)}
    assert p4_terms == {p4: 1, p3: -2, clique(3).canonical(): -1,
                        clique(2).canonical(): 1}

    g = gen.triangle_rich(20, 3, seed=5)
    for p in (c4, p4):
        store = morphlib.CountStore()
        gsig = warm_with_brute_homs(p, g, store)
        cand = morphlib.derive(p, store, gsig)
        assert cand.complete
        assert cand.value * p.aut_order() == brute_inj(p, g)
        assert analysis.morph_check(cand).ok


# -- derived identities == brute force --------------------------------------------

def _pattern_from_bits(n, bits, labels=None):
    pairs = [(i, j) for i in range(n) for j in range(i + 1, n)]
    return Pattern(n, [e for t, e in enumerate(pairs) if bits >> t & 1],
                   labels=labels)


def _graph_for(kind, seed, num_labels=0):
    return (gen.erdos_renyi(9, 3.0, seed=seed, num_labels=num_labels)
            if kind == "er"
            else gen.rmat(3, 3.0, seed=seed, num_labels=num_labels))


def _check_derived(p, g):
    """derive() over brute-warmed quotient homs reproduces the
    brute-force injective count integer-exactly, and morph_check holds."""
    store = morphlib.CountStore()
    gsig = warm_with_brute_homs(p, g, store)
    cand = morphlib.derive(p, store, gsig)
    assert cand.complete
    assert cand.value * p.aut_order() == brute_inj(p, g)
    assert analysis.morph_check(cand).ok


def test_derived_identity_matches_brute_force_hypothesis():
    """Property test: random connected <=5-vertex patterns on er/rmat
    generator graphs — the derived inclusion–exclusion coefficients
    reproduce brute-force injective counts exactly."""
    pytest.importorskip("hypothesis")
    from hypothesis import assume, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(3, 5), bits=st.integers(1, (1 << 10) - 1),
           kind=st.sampled_from(["er", "rmat"]), seed=st.integers(0, 3))
    def prop(n, bits, kind, seed):
        p = _pattern_from_bits(n, bits)
        assume(p.is_connected() and p.m > 0)
        _check_derived(p, _graph_for(kind, seed))

    prop()


def test_labelled_derived_identity_matches_brute_force_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import assume, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=15, deadline=None)
    @given(bits=st.integers(1, (1 << 6) - 1),
           labels=st.tuples(*[st.integers(0, 1)] * 4),
           seed=st.integers(0, 3))
    def prop(bits, labels, seed):
        p = _pattern_from_bits(4, bits, labels=labels)
        assume(p.is_connected())
        _check_derived(p, _graph_for("er", seed, num_labels=2))

    prop()


def test_derived_identity_matches_brute_force_seeded():
    """Deterministic sweep of the same property — runs even where
    hypothesis isn't installed (it is optional across this suite)."""
    rng = np.random.default_rng(7)
    checked = 0
    while checked < 12:
        n = int(rng.integers(3, 6))
        bits = int(rng.integers(1, 1 << (n * (n - 1) // 2)))
        labels = (tuple(int(x) for x in rng.integers(0, 2, n))
                  if rng.integers(0, 2) else None)
        p = _pattern_from_bits(n, bits, labels=labels)
        if not (p.is_connected() and p.m > 0):
            continue
        kind = "er" if rng.integers(0, 2) else "rmat"
        g = _graph_for(kind, int(rng.integers(0, 4)),
                       num_labels=2 if labels is not None else 0)
        _check_derived(p, g)
        checked += 1


# -- morph_check is a real check --------------------------------------------------

def test_morph_check_catches_corruption():
    c4 = cycle(4).canonical()
    good = morphlib.MorphCandidate(
        pattern=c4, terms=quotient_terms(c4), missing=(),
        divisor=c4.aut_order())
    assert analysis.morph_check(good).ok
    # flip one coefficient -> the complete-graph endpoints diverge
    bad_terms = tuple((c if q.m != c4.m else -c, q)
                      for c, q in quotient_terms(c4))
    bad = morphlib.MorphCandidate(pattern=c4, terms=bad_terms, missing=(),
                                  divisor=c4.aut_order())
    r = analysis.morph_check(bad)
    assert not r.ok and "morph-endpoint-complete" in r.codes()
    # wrong automorphism divisor
    off = morphlib.MorphCandidate(pattern=c4, terms=quotient_terms(c4),
                                  missing=(), divisor=3)
    assert "morph-divisor" in analysis.morph_check(off).codes()


# -- lattice explorer -------------------------------------------------------------

def test_morph_neighbours_and_family():
    tri, wedge = clique(3).canonical(), chain(3).canonical()
    assert morphlib.morph_neighbours(wedge) == (tri,)
    assert morphlib.morph_neighbours(tri) == (wedge,)
    fam4, fam5 = morphlib.motif_family(4), morphlib.motif_family(5)
    assert len(fam4) == 6 and len(fam5) == 21
    assert all(p.is_connected() and p.n == 4 for p in fam4)
    # distance-2 frontier from C4 reaches everything but the clique end
    assert len(morphlib.morph_neighbours(cycle(4), distance=3)) == 5


# -- store persistence ------------------------------------------------------------

def test_count_store_disk_roundtrip_and_version_drift(tmp_path):
    store = morphlib.CountStore(str(tmp_path))
    assert store.put("g1", "hom", chain(3), 42.0) == 1
    assert store.put("g1", "hom", chain(3), 42) == 0     # idempotent
    store.put("g1", "inj", cycle(4), 7)
    store.sync()
    fresh = morphlib.CountStore(str(tmp_path))
    assert fresh.get("g1", "hom", chain(3)) == 42
    assert fresh.get("g1", "inj", cycle(4)) == 7
    assert fresh.held_hom_keys("g1") == {f"hom:{pattern_key(chain(3))}"}
    # stamp a future format version: clean miss, counted
    f = fresh._file("g1")
    with open(f) as fh:
        doc = json.load(fh)
    doc["version"] = morphlib.MORPH_FORMAT_VERSION + 1
    with open(f, "w") as fh:
        fh.write(json.dumps(doc))
    drifted = morphlib.CountStore(str(tmp_path))
    assert drifted.get("g1", "hom", chain(3)) is None
    assert drifted.stats["format_misses"] == 1


def test_count_store_sync_failure_is_counted(tmp_path, monkeypatch):
    store = morphlib.CountStore(str(tmp_path))
    store.put("g1", "hom", chain(3), 5)

    def boom(*a, **k):
        raise OSError("read-only store dir")
    monkeypatch.setattr(os, "replace", boom)
    store.sync()                      # must not raise
    assert store.stats["sync_failures"] == 1
    assert store.get("g1", "hom", chain(3)) == 5   # memory tier intact


# -- harvest + compile fast path --------------------------------------------------

def test_compiled_count_harvests_into_store():
    g = gen.erdos_renyi(24, 4.0, seed=2)
    store = morphlib.CountStore()
    cp = compiler.compile((chain(4),), g, cache=False, morph=store)
    cp.count(chain(4))
    gsig = graph_signature(g)
    held = store._mem[gsig]
    assert f"inj:{pattern_key(chain(4))}" in held
    assert held[f"inj:{pattern_key(chain(4))}"] == brute_inj(chain(4), g)
    assert any(k.startswith("hom:") for k in held)


def test_fast_path_serves_family_member_without_search():
    g = gen.erdos_renyi(48, 5.0, seed=2)
    store = morphlib.CountStore()
    # warm: the 5-path compiles decomposed-subset, whose scalar quotient
    # homs (P3, K2 among them) close the wedge identity
    compiler.compile((chain(5),), g, cache=False, morph=store).count(chain(5))
    hits0 = obs.get("morph.hits", 0.0)
    cp = compiler.compile((chain(3),), g, cache=False, morph=store)
    assert cp.plan.meta.get("morph") is True
    assert cp.plan.meta["styles"] == {pattern_key(chain(3)): "morph"}
    assert obs.get("morph.hits", 0.0) == hits0 + 1
    direct = compiler.compile((chain(3),), g, cache=False).count(chain(3))
    assert cp.count(chain(3)) == direct


def test_warm_store_serves_size4_family_exactly():
    """Three 5-vertex plans warm the store: the 5-path, the gem and the
    tailed 4-cycle.  Every size-4 motif compiled against it counts what
    the engine counts directly, and ``morph.hits`` counts exactly the
    members served algebraically."""
    from repro.core.apct import APCT
    g = gen.erdos_renyi(48, 5.0, seed=3)
    apct = APCT(g, num_samples=512)
    store = morphlib.CountStore()
    gem = Pattern(5, [(0, 1), (1, 2), (2, 3), (0, 4), (1, 4), (2, 4),
                      (3, 4)])
    tailed_c4 = Pattern(5, [(0, 1), (1, 2), (2, 3), (0, 3), (3, 4)])
    for p in (chain(5), gem, tailed_c4):
        compiler.compile((p,), g, apct=apct, cache=False,
                         morph=store).count(p)
    hits0 = obs.get("morph.hits", 0.0)
    family = morphlib.motif_family(4)
    eng = CountingEngine(g)
    served = 0
    for p in family:
        cp = compiler.compile((p,), g, apct=apct, cache=False, morph=store)
        served += bool(cp.plan.meta.get("morph"))
        assert cp.count(p) == eng.edge_induced(p), sorted(p.edges)
    assert obs.get("morph.hits", 0.0) - hits0 == served
    assert 2 * served >= len(family), served


def test_missing_counts_fall_back_to_search():
    g = gen.erdos_renyi(48, 5.0, seed=2)
    store = morphlib.CountStore()           # empty: nothing closes
    misses0 = obs.get("morph.missing_compiles", 0.0)
    cp = compiler.compile((cycle(4),), g, cache=False, morph=store)
    assert cp.plan.meta.get("morph") is None       # searched normally
    assert obs.get("morph.missing_compiles", 0.0) == misses0 + 1
    direct = compiler.compile((cycle(4),), g, cache=False).count(cycle(4))
    assert cp.count(cycle(4)) == direct


def test_held_hom_prices_zero_in_costing():
    g = gen.erdos_renyi(40, 4.0, seed=1)
    from repro.core.apct import APCT
    apct = APCT(g)
    cand = frontend.direct_candidate(chain(3))
    hom_nodes = [nd for nd in cand.nodes if nd.key.startswith("hom:")
                 and not getattr(nd, "free", ())]
    assert hom_nodes
    node = hom_nodes[0]
    assert costing.node_cost(node, apct, g.n) > 0.0
    assert costing.node_cost(node, apct, g.n, held={node.key}) == 0.0
    held = {nd.key for nd in hom_nodes}
    free_cost = costing.candidate_cost(cand, apct, g.n, {}, held=held)
    assert free_cost < costing.candidate_cost(cand, apct, g.n, {})


# -- morph-off stays bit-for-bit --------------------------------------------------

def test_morph_off_unchanged_and_cache_unpolluted():
    g = gen.erdos_renyi(48, 5.0, seed=2)
    cache = PlanCache()
    p = chain(3)
    baseline = compiler.compile((p,), g, cache=False).plan.to_json()
    # a morph compile (fast path) must not write the plan cache
    store = morphlib.CountStore()
    compiler.compile((chain(5),), g, cache=False, morph=store).count(chain(5))
    cp = compiler.compile((p,), g, cache=cache, morph=store)
    assert cp.plan.meta.get("morph") is True
    assert plan_key((p,), g) not in cache
    # ...and a later morph=False compile is byte-identical to baseline
    after = compiler.compile((p,), g, cache=cache, morph=False)
    assert after.plan.meta.get("morph") is None
    assert after.plan.to_json() == baseline


# -- consumers --------------------------------------------------------------------

def test_mining_engine_threads_morph():
    from repro.core.engine import MiningEngine
    g = gen.erdos_renyi(48, 5.0, seed=4)
    store = morphlib.CountStore()
    eng = MiningEngine(g, morph=store)
    plain = MiningEngine(g)
    for p in (chain(4), chain(3), clique(3)):
        assert eng.get_pattern_count(p) == plain.get_pattern_count(p)
    assert eng.compiler_fallbacks == 0
    assert len(store) > 0


def test_batcher_threads_morph():
    from repro.serve.batching import PatternQueryBatcher, PatternRequest
    g = gen.erdos_renyi(48, 5.0, seed=4)
    store = morphlib.CountStore()
    b = PatternQueryBatcher(g, cache=PlanCache(), morph=store)
    plain = PatternQueryBatcher(g, cache=PlanCache())
    for i, p in enumerate((chain(4), chain(3))):
        b.submit(PatternRequest(uid=i, patterns=(p,)))
        plain.submit(PatternRequest(uid=i, patterns=(p,)))
    b.run_to_completion()
    plain.run_to_completion()
    got = {r.uid: dict(r.counts) for r in b.finished}
    want = {r.uid: dict(r.counts) for r in plain.finished}
    assert not any(r.error for r in b.finished)
    assert got == want
    assert len(store) > 0


def test_fsm_feeds_and_reads_count_store():
    from repro.core.fsm import fsm
    g = gen.erdos_renyi(40, 4.0, seed=6, num_labels=2)
    store = morphlib.CountStore()
    with_store = fsm(g, min_support=2, max_vertices=3, count_store=store)
    without = fsm(g, min_support=2, max_vertices=3)
    assert with_store.frequent == without.frequent
    assert with_store.fallbacks == 0
    assert len(store) > 0                 # levels harvested their counts


# -- satellites -------------------------------------------------------------------

def test_plancache_utime_failure_counted(tmp_path, monkeypatch):
    g = gen.erdos_renyi(40, 4.0, seed=0)
    cache = PlanCache(str(tmp_path), max_disk_entries=8)
    p = chain(3)
    compiler.compile((p,), g, cache=cache)
    key = plan_key((p,), g)
    before = obs.get("plancache.utime_failures", 0.0)

    def boom(*a, **k):
        raise OSError("read-only cache dir")
    monkeypatch.setattr(os, "utime", boom)
    assert cache.get(key) is not None     # memory-tier recency refresh
    cache._mem.clear()
    assert cache.get(key) is not None     # cold disk read
    assert obs.get("plancache.utime_failures", 0.0) >= before + 2


def test_graph_invalidate_signature():
    g = Graph(4, np.array([[0, 1], [1, 2]]))
    s1 = graph_signature(g)
    assert graph_signature(g) == s1       # memoised
    g.edges = np.asarray([[0, 1], [1, 2], [2, 3]], g.edges.dtype)
    g.m = 3
    assert graph_signature(g) == s1       # stale without invalidation
    g.invalidate_signature()
    assert graph_signature(g) != s1
    assert g._csr is None and g._dense is None
