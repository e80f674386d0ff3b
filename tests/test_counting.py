"""Counting engine vs brute-force ground truth (+ property tests)."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.counting import (CountingEngine, brute_force_edge_induced,
                                 brute_force_vertex_induced, solve_overlay)
from repro.core.motifs import motif_patterns
from repro.core.pattern import (Pattern, chain, clique, cycle,
                                tailed_triangle)
from repro.graph.generators import erdos_renyi, small_world, triangle_rich
from repro.graph.storage import Graph

PATTERNS = [chain(3), clique(3), chain(4), cycle(4), clique(4),
            tailed_triangle(), chain(5), cycle(5),
            Pattern(5, [(0, 1), (0, 2), (1, 2), (1, 3), (2, 4)])]

GRAPHS = [erdos_renyi(22, 4.0, seed=1), small_world(24, 4, 0.3, seed=2),
          triangle_rich(24, 4, seed=3)]


@pytest.mark.parametrize("gi", range(len(GRAPHS)))
@pytest.mark.parametrize("pi", range(len(PATTERNS)))
def test_edge_induced_matches_brute_force(gi, pi):
    g, p = GRAPHS[gi], PATTERNS[pi]
    eng = CountingEngine(g)
    assert abs(eng.edge_induced(p) - brute_force_edge_induced(g, p)) < 1e-6


@pytest.mark.parametrize("p", [chain(3), clique(3), cycle(4), chain(4),
                               tailed_triangle()])
def test_vertex_induced_three_ways(p):
    g = GRAPHS[0]
    eng = CountingEngine(g)
    brute = brute_force_vertex_induced(g, p)
    assert abs(eng.vertex_induced(p) - brute) < 1e-6
    assert abs(eng.vind_inj_oracle(p) / p.aut_order() - brute) < 1e-6


def test_paper_running_example():
    # Figure 2 graph: vertices 0..3, edges 01,02,12,13,23
    g = Graph(4, [(0, 1), (0, 2), (1, 2), (1, 3), (2, 3)])
    eng = CountingEngine(g)
    assert eng.edge_induced(clique(3)) == 2          # two triangles
    assert eng.edge_induced(chain(3)) == 8           # paper: 8 edge-induced
    assert eng.vertex_induced(chain(3)) == 2         # paper: 8 - 3*2 = 2
    assert eng.vertex_induced(clique(3)) == 2


def test_decomposition_choice_does_not_change_counts():
    from repro.core.decomposition import cutting_sets
    g = GRAPHS[1]
    eng = CountingEngine(g)
    p = chain(5)
    base = eng.edge_induced(p, cut=None)
    for cut in cutting_sets(p)[:6]:
        assert abs(eng.edge_induced(p, cut=cut) - base) < 1e-9


def test_motif_table_sums():
    g = GRAPHS[0]
    eng = CountingEngine(g)
    table = eng.motif_table(3)
    total_subsets = 0
    import itertools
    for vs in itertools.combinations(range(g.n), 3):
        edges = sum(g.has_edge(a, b) for a, b in itertools.combinations(vs, 2))
        if edges >= 2:
            # connected 3-subgraph
            total_subsets += 1
    assert abs(sum(table.values()) - total_subsets) < 1e-6


def test_motif_table_cost_model_cuts_match_direct():
    """The 5-motif table over cost-model-chosen cuts on one shared engine
    equals the table solved from direct counts, each on a fresh engine.
    At this size the model decomposes only some 5-vertex patterns."""
    from repro.core.engine import MiningEngine
    g, k = GRAPHS[0], 5
    miner = MiningEngine(g)
    pats = motif_patterns(k)
    cuts = {p: miner.choose_cut(p) for p in pats}
    assert any(c is not None for c in cuts.values())
    got = miner.counter.motif_table(k, cuts=cuts)
    want = solve_overlay(k, {p: CountingEngine(g).edge_induced(p, cut=None)
                             for p in pats})
    assert got.keys() == want.keys()
    for p in want:
        assert abs(got[p] - want[p]) < 1e-6 * max(1.0, abs(want[p])) + 1e-6


def test_memoization_reuse_across_patterns():
    g = GRAPHS[0]
    eng = CountingEngine(g)
    for p in motif_patterns(4):
        eng.edge_induced(p)
    assert eng.stats["hom_hits"] > 0           # cross-pattern reuse


@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_property_random_graph_chain4(seed):
    g = erdos_renyi(16, 3.0, seed=seed)
    eng = CountingEngine(g)
    assert abs(eng.edge_induced(chain(4))
               - brute_force_edge_induced(g, chain(4))) < 1e-6


def test_counts_exact_at_large_magnitude():
    # x64 accumulation: star counts ~ sum(deg choose k) can exceed 2^24
    g = erdos_renyi(600, 40.0, seed=7)
    eng = CountingEngine(g)
    from repro.core.pattern import star
    deg = g.degrees.astype(object)
    want = sum(int(d) * int(d - 1) * int(d - 2) // 6 for d in deg)
    got = eng.edge_induced(star(4))
    assert got == want
