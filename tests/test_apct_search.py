"""APCT estimator, cost model, and decomposition-space search."""
import math

import numpy as np
import pytest

from repro.core import cost_model as CM
from repro.core import search as S
from repro.core.apct import APCT, _bfs_tree, estimate_inj
from repro.core.counting import CountingEngine
from repro.core.motifs import motif_patterns
from repro.core.pattern import Pattern, chain, clique
from repro.graph.generators import erdos_renyi, triangle_rich

G = triangle_rich(120, 8, seed=4)


@pytest.fixture(scope="module")
def apct():
    return APCT(G, num_samples=20_000)


def test_apct_accurate_on_frequent_patterns(apct):
    eng = CountingEngine(G)
    for p in [chain(3), clique(3), chain(4)]:
        exact = eng.inj(p)
        est = apct.query(p)
        if exact > 100:
            assert 0.5 * exact <= est <= 2.0 * exact, (p, est, exact)


def test_apct_miss_insertion(apct):
    before = apct.misses
    p6 = motif_patterns(6)[3]
    apct.query(p6)                        # size-6: not profiled
    assert apct.misses == before + 1
    apct.query(p6)                        # now cached
    assert apct.misses == before + 1


def test_apct_unbiased_estimator():
    eng = CountingEngine(G)
    exact = eng.inj(clique(3))
    ests = [estimate_inj(G, clique(3), 40_000, seed=s) for s in range(5)]
    assert abs(np.mean(ests) - exact) / exact < 0.25


def test_cost_model_prefers_cheap_patterns(apct):
    # chain counting costs more than clique counting at equal size (paper §2.4)
    c_chain = CM.pattern_cost(chain(5), None, apct, G.n)
    c_clique = CM.pattern_cost(clique(5), None, apct, G.n)
    assert c_chain > c_clique


def test_cost_model_reuse_reduces_joint_cost(apct):
    pats = motif_patterns(4)
    sep = sum(CM.pattern_cost(p, None, apct, G.n) for p in pats)
    joint = CM.application_cost([(p, None) for p in pats], apct, G.n)
    assert joint <= sep


def test_circulant_no_worse_than_separate(apct):
    pats = motif_patterns(4)
    r_sep = S.separate_tuning(pats, apct, G.n)
    r_circ = S.circulant_tuning(pats, apct, G.n)
    assert r_circ.cost <= r_sep.cost + 1e-9
    assert len(r_circ.cuts) == len(pats)


def test_search_methods_return_valid_cuts(apct):
    pats = motif_patterns(4)
    for name, fn in S.METHODS.items():
        r = fn(pats, apct, G.n)
        assert len(r.cuts) == len(pats), name
        from repro.core.decomposition import candidates
        for p, cut in zip(pats, r.cuts):
            assert cut in candidates(p), (name, p, cut)


def test_automine_model_underestimates_clustered_graphs(apct):
    """Fig 19 argument: the random-graph model misses structural locality,
    so its clique trip-count estimate falls far below the APCT estimate on
    a clustered graph."""
    d = float(np.mean(G.degrees))
    am = CM.plan_cost_automine(clique(4), tuple(range(4)), G.n, d)
    ours = CM.plan_cost_apct(clique(4), tuple(range(4)), apct, G.n)
    eng = CountingEngine(G)
    exact_k4 = eng.inj(clique(4))
    if exact_k4 > 0:
        assert ours > am


# -- the estimator's edge test and the lazily filled table ------------------

PATTERNS_2_TO_5 = [p for k in range(2, 6) for p in motif_patterns(k)]


def _estimate_by_rows(g, p, num_samples, seed):
    """``estimate_inj`` with the non-tree edges tested row by row: one
    ``searchsorted`` per sample within the CSR row of its first end."""
    if g.m == 0 or p.n > g.n:
        return 0.0
    rng = np.random.default_rng(seed)
    offs, nbrs = g.csr
    deg = np.diff(offs)
    order, parent = _bfs_tree(p)
    verts = np.zeros((p.n, num_samples), np.int64)
    weight = np.full(num_samples, float(g.n))
    valid = np.ones(num_samples, bool)
    verts[order[0]] = rng.integers(0, g.n, num_samples)
    for v in order[1:]:
        par = verts[parent[v]]
        d = deg[par]
        valid &= d > 0
        d_safe = np.maximum(d, 1)
        pick = (rng.random(num_samples) * d_safe).astype(np.int64)
        verts[v] = nbrs[np.minimum(offs[par] + pick, len(nbrs) - 1)]
        weight *= d_safe
    for i in range(p.n):
        for j in range(i + 1, p.n):
            valid &= verts[i] != verts[j]
    tree = {(min(v, parent[v]), max(v, parent[v])) for v in order[1:]}
    for (u, v) in p.edges - tree:
        a, b = verts[u], verts[v]
        lo, hi = offs[a], offs[a + 1]
        pos = np.array([np.searchsorted(nbrs[l:h], x)
                        for l, h, x in zip(lo, hi, b)])
        valid &= (lo + pos < hi) & \
            (nbrs[np.minimum(lo + pos, len(nbrs) - 1)] == b)
    if g.labels is not None and p.labels is not None:
        for v in range(p.n):
            valid &= g.labels[verts[v]] == np.array(p.labels[v])
    return float(np.sum(weight * valid) / num_samples)


@pytest.mark.parametrize("seed", [0, 7])
@pytest.mark.parametrize("labelled", [False, True])
@pytest.mark.parametrize("p", PATTERNS_2_TO_5, ids=repr)
def test_edge_test_matches_row_search(p, labelled, seed):
    """One search over the CSR's global keys decides every non-tree edge
    exactly as the per-row search does, valid and invalid samples alike,
    so the estimate is the same float."""
    g = triangle_rich(90, 6, seed=seed, num_labels=2 if labelled else 0)
    if labelled:
        p = Pattern(p.n, p.edges, tuple(v % 2 for v in range(p.n)))
    assert estimate_inj(g, p, 1024, seed) == \
        _estimate_by_rows(g, p, 1024, seed)


@pytest.fixture(scope="module")
def lazy_apct():
    return APCT(G, max_profile_edges=200, num_samples=4096, seed=3)


@pytest.mark.parametrize("p", PATTERNS_2_TO_5, ids=repr)
def test_lazy_query_is_the_eager_estimate(lazy_apct, p):
    """A lazily filled entry is exactly what profiling it up front gave:
    the same estimator on the same profile graph with the same seed."""
    assert lazy_apct.profile_graph.m == 200 < G.m
    want = estimate_inj(lazy_apct.profile_graph, p.canonical(), 4096, 3)
    assert lazy_apct.query(p) == want
    assert lazy_apct.query(p.relabel(tuple(reversed(range(p.n))))) == want


def test_motif4_compile_estimates_only_what_it_queries(monkeypatch):
    """A 4-motif compile profiles only the patterns costing asks for:
    none of 5 vertices, and one ``apct.estimates`` per distinct
    canonical skeleton queried."""
    from repro import compiler, obs
    queried = set()
    query = APCT.query

    def recording(self, p):
        queried.add(Pattern(p.n, p.edges).canonical())
        return query(self, p)

    monkeypatch.setattr(APCT, "query", recording)
    g = erdos_renyi(60, 5, seed=2)
    before = obs.get("apct.estimates")
    compiler.compile(motif_patterns(4), g, cache=False)
    made = obs.get("apct.estimates") - before
    assert queried and max(p.n for p in queried) <= 4
    assert made == len(queried)
