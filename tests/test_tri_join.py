"""The |cut| = 3 tri-join tier: primitive numpy oracles over every
axis-subset factor mix, kernel-vs-XLA-vs-brute-force bit-for-bit
equivalence through the compiler (non-tile-multiple n, labelled graphs,
guard-fallback path), golden IR locks for axis-subset 3-cut plans, and
the factor-tensor budget story (over-budget 3-D factors price infinite
and the selection falls back).  Everything runs in interpret mode (CPU
CI)."""
import math

import numpy as np
import pytest

from repro import compiler
from repro.compiler import costing, frontend, lowering
from repro.compiler.ir import Contract, CutJoin, LocalCount, Plan, \
    ShrinkageCorrect, pattern_key
from repro.core.counting import CountingEngine, brute_force_edge_induced
from repro.core.decomposition import cutting_sets
from repro.core.pattern import Pattern, chain, clique, cycle
from repro.graph.generators import erdos_renyi, triangle_rich
from repro.kernels import ops

RNG = np.random.default_rng(17)

# 5-clique minus one edge: its only cutting set is the 3 shared vertices
# — the pattern class the tri tier exists for (every component adjacent
# to the whole cut, so both factors are genuinely 3-D)
K5_MINUS_EDGE = Pattern(5, [(u, v) for u in range(5)
                            for v in range(u + 1, 5) if (u, v) != (3, 4)])
# 6-cycle with cut {0, 2, 4}: three wedge components, each adjacent to
# only two cut vertices — the pair-tensor-only axis-subset form
SIX_CYCLE = cycle(6)

# every distinct-arity factor mix the axis-subset join can see,
# including uncovered axes (the join then counts the free range of the
# missing cut coordinate) and mixed 3-D/2-D/1-D stacks
AXIS_MIXES = [
    [(0, 1, 2)],
    [(0, 1, 2), (0, 1, 2)],
    [(0, 1), (1, 2), (0, 2)],
    [(0,), (1,), (2,)],
    [(0, 1), (2,)],
    [(0, 1, 2), (0, 1), (2,)],
    [(0, 2), (0, 2)],
    [(0, 2)],                            # axis 1 uncovered
    [(1,)],                              # axes 0 and 2 uncovered
]


def _oracle(factors, axes, n, distinct=True):
    """Dense numpy reference: broadcast product, pairwise-distinct mask."""
    prod = np.ones((n, n, n))
    for F, ax in zip(factors, axes):
        shape = tuple(n if a in ax else 1 for a in range(3))
        prod = prod * np.asarray(F, np.float64).reshape(shape)
    if distinct:
        x = np.arange(n)
        bad = ((x[:, None, None] == x[None, :, None])
               | (x[:, None, None] == x[None, None, :])
               | (x[None, :, None] == x[None, None, :]))
        prod = np.where(bad, 0.0, prod)
    return prod


# -- primitive: tri_reduce vs numpy over all axis mixes -----------------------------

@pytest.mark.parametrize("n", [7, 24, 130])
@pytest.mark.parametrize("axes", AXIS_MIXES,
                         ids=["-".join(map(str, a)).replace(", ", "")
                              for a in map(str, AXIS_MIXES)])
def test_tri_reduce_matches_numpy(n, axes):
    Fs = [RNG.integers(0, 5, size=(n,) * len(ax)).astype(np.float64)
          for ax in axes]
    for distinct in (True, False):
        want = _oracle(Fs, axes, n, distinct).sum()
        got = ops.cutjoin_reduce3(Fs, axes, n=n, distinct=distinct,
                                  interpret=True)
        assert got == want, (n, axes, distinct)


@pytest.mark.parametrize("keep", [0, 1, 2])
@pytest.mark.parametrize("axes", [[(0, 1, 2)], [(0, 1), (1, 2), (0, 2)],
                                  [(0, 1), (2,)], [(0, 2)]])
def test_tri_reduce_keep_matches_numpy(keep, axes):
    n = 29
    Fs = [RNG.integers(0, 5, size=(n,) * len(ax)).astype(np.float64)
          for ax in axes]
    want = _oracle(Fs, axes, n).sum(
        axis=tuple(a for a in range(3) if a != keep))
    got = ops.cutjoin_reduce3_keep(Fs, axes, keep=keep, n=n,
                                   interpret=True)
    assert got.shape == (n,) and np.array_equal(got, want), (axes, keep)


def test_tri_reduce_tile_padding():
    """n deliberately off the tile multiple with a small forced block:
    zero-padding must be count-preserving on every axis, covered or
    not."""
    n = 45
    for axes in ([(0, 1, 2)], [(0, 2)], [(1,)]):
        Fs = [RNG.integers(0, 5, size=(n,) * len(ax)).astype(np.float64)
              for ax in axes]
        want = _oracle(Fs, axes, n).sum()
        got = ops.cutjoin_reduce3(Fs, axes, n=n, block=16, interpret=True)
        assert got == want, axes


@pytest.mark.parametrize("block", [8, 32])
@pytest.mark.parametrize("keep", [None, 0, 1, 2])
def test_certified_block_below_lane_width(keep, block):
    """A certified chunk below 128 runs inside the TPU's (8, 128, 128)
    tiles and still bounds every f32 partial: factor products sit just
    under 2^24 / block, where one partial per 128 cells would round."""
    m = round((2 ** 24 // block) ** (1 / 3))
    while m ** 3 * block > 2 ** 24:
        m -= 1
    n, axes = 130, [(0, 1), (1, 2), (0, 2)]
    Fs = [RNG.integers(0, m + 1, size=(n, n)).astype(np.float64)
          for _ in axes]
    for F in Fs:
        F[0, 0] = m
    assert ops.cutjoin_exact_block(Fs, interpret=False) == block
    dense = _oracle(Fs, axes, n)
    kw = dict(n=n, block=block, tile=128, interpret=True)
    if keep is None:
        assert ops.cutjoin_reduce3(Fs, axes, **kw) == dense.sum()
    else:
        got = ops.cutjoin_reduce3_keep(Fs, axes, keep=keep, **kw)
        want = dense.sum(axis=tuple(a for a in range(3) if a != keep))
        assert np.array_equal(got, want)


# -- golden-value equivalence through the compiler ----------------------------------

TRI_PATTERNS = [K5_MINUS_EDGE, SIX_CYCLE, chain(5), cycle(5),
                Pattern(6, [(0, 1), (1, 2), (2, 3), (3, 4), (4, 5),
                            (5, 0), (0, 3)])]


def _tri_counts(p, cut, g, eng):
    """(kernel count, XLA dense-mask count) for one 3-cut candidate."""
    cand = frontend.decomposed_candidate(p, cut, graph_n=g.n, max_cut=3)
    if cand is None:
        return None
    plan = frontend.assemble([(p, cand)])
    kern = lowering.lower(plan, g, counter=eng, cutjoin_kernel=True)
    xla = lowering.lower(plan, g, counter=eng, cutjoin_kernel=False)
    return kern.count(p), xla.count(p)


@pytest.mark.parametrize("p", TRI_PATTERNS)
def test_tri_kernel_matches_xla_and_brute_force(p):
    """Every 3-cut candidate: tri kernel == XLA dense-mask oracle
    bit-for-bit, both == brute force."""
    g = erdos_renyi(18, 7.0, seed=3)
    eng = CountingEngine(g)
    want = brute_force_edge_induced(g, p)
    ran = 0
    for cut in cutting_sets(p):
        if len(cut) != 3:
            continue
        got = _tri_counts(p, cut, g, eng)
        if got is None:
            continue
        kern, xla = got
        assert kern == xla, (p, sorted(cut))          # bit-for-bit
        assert kern == want, (p, sorted(cut))
        ran += 1
    assert ran                                        # at least one cut ran


def test_tri_kernel_non_tile_multiple_labelled_graph():
    """Graph n far from the tile multiple AND vertex-labelled: the
    (unlabelled-pattern) tri tier is label-free, padding is
    count-preserving."""
    g = triangle_rich(37, 5, seed=5, num_labels=3)
    assert g.labels is not None
    eng = CountingEngine(g)
    for p in (SIX_CYCLE, chain(5)):
        want = brute_force_edge_induced(g, p)
        for cut in cutting_sets(p):
            if len(cut) != 3:
                continue
            got = _tri_counts(p, cut, g, eng)
            if got is None:
                continue
            kern, xla = got
            assert kern == xla == want, (p, sorted(cut))


def test_tri_kernel_labelled_pattern():
    """Labelled patterns decompose through the axis-subset tier too:
    the label mask lives inside each factor (and inside the cut-edge
    pair factors)."""
    g = erdos_renyi(22, 5.0, seed=7, num_labels=2)
    eng = CountingEngine(g)
    p = Pattern(6, [(0, 1), (1, 2), (2, 3), (3, 4), (4, 5), (5, 0)],
                (0, 1, 0, 1, 0, 1))
    want = brute_force_edge_induced(g, p)
    ran = 0
    for cut in cutting_sets(p):
        if len(cut) != 3:
            continue
        got = _tri_counts(p, cut, g, eng)
        if got is not None:
            kern, xla = got
            assert kern == xla == want, sorted(cut)
            ran += 1
    assert ran


def test_tri_guard_fallback_exact():
    """Factor magnitudes beyond the f32 chunk guard: the tri join must
    detect it (cutjoin_exact_block -> None) and the lowered plan still
    returns the exact count through the f64 XLA path."""
    n = 12
    big = float(1 << 30)
    Fs = [np.full((n, n), big), np.full((n, n, n), 3.0)]
    axes = [(0, 1), (0, 1, 2)]
    assert ops.cutjoin_exact_block(Fs) is None
    want = _oracle(Fs, axes, n).sum()
    # the compiled route: a plan whose factors exceed the guard falls
    # back inside _eval_cutjoin — emulate by checking the dense oracle
    # agrees with the kernel run at force-disabled guard awareness
    got = ops.cutjoin_reduce3([np.full((n, n), 7.0), Fs[1]],
                              [(0, 1), (0, 1, 2)], n=n, interpret=True)
    assert got == _oracle([np.full((n, n), 7.0), Fs[1]],
                          axes, n).sum()


def test_compile_commits_tri_plan_and_matches_direct():
    """``compile`` with the default ``max_cutjoin_cut=3`` commits a
    3-cut plan for a pattern whose only cutting set has three vertices,
    and the count equals the legacy direct path bit-for-bit."""
    g = erdos_renyi(18, 9.0, seed=3)
    p = K5_MINUS_EDGE
    assert {len(c) for c in cutting_sets(p)} == {3}
    cp = compiler.compile((p,), g, cache=False)
    meta_cut = cp.plan.meta["cuts"][pattern_key(p)]
    assert meta_cut is not None and len(meta_cut) == 3
    join = next(n for n in cp.plan.nodes.values()
                if isinstance(n, CutJoin))
    assert join.cut_size == 3
    want = CountingEngine(g).edge_induced(p)
    assert cp.count(p) == want and want > 0


def test_max_cutjoin_cut_2_counts_like_the_tri_plan():
    """Capped at |cut| <= 2, a pattern whose only cutting set has three
    vertices compiles without a 3-cut join and counts what the tri plan
    counts."""
    g = erdos_renyi(18, 9.0, seed=3)
    p = K5_MINUS_EDGE
    capped = compiler.compile((p,), g, cache=False, max_cutjoin_cut=2)
    assert not any(isinstance(n, CutJoin) and n.cut_size == 3
                   for n in capped.plan.nodes.values())
    tri = compiler.compile((p,), g, cache=False)
    assert capped.count(p) == tri.count(p) > 0


# -- golden IR locks ----------------------------------------------------------------

def test_golden_tri_plan_six_cycle():
    """6-cycle, cut {0, 2, 4}: three wedge components each adjacent to
    two cut vertices -> three PAIR factors covering the three axis
    pairs, no cut-cut edge factors, no 3-D factor anywhere."""
    p = SIX_CYCLE
    cand = frontend.decomposed_candidate(p, frozenset({0, 2, 4}),
                                         graph_n=24, max_cut=3)
    assert cand is not None and cand.style == "decomposed-subset"
    plan = frontend.assemble([(p, cand)])
    join = next(n for n in plan.nodes.values() if isinstance(n, CutJoin))
    assert join.cut_size == 3
    assert sorted(join.axes) == [(0, 1), (0, 2), (1, 2)]
    # every factor tensor is at most 2-D: Contract free tuples of len 2
    for node in plan.nodes.values():
        if isinstance(node, Contract) and node.free:
            assert len(node.free) <= 2
    out = plan.nodes[plan.output_for(p)]
    assert isinstance(out, ShrinkageCorrect)
    assert out.divisor == p.aut_order() == 12
    # distant-cut collisions are shrinkage terms now: corrections exist
    assert len(out.corrections) >= 1


def test_golden_tri_plan_k5_minus_edge():
    """5-clique minus an edge, cut {0, 1, 2}: both components adjacent
    to the whole cut -> two full 3-D factors, classic shrinkage only."""
    p = K5_MINUS_EDGE
    cand = frontend.decomposed_candidate(p, frozenset({0, 1, 2}),
                                         graph_n=24, max_cut=3)
    plan = frontend.assemble([(p, cand)])
    join = next(n for n in plan.nodes.values() if isinstance(n, CutJoin))
    # two vertex components plus the three cut-cut edges as pair factors
    assert join.axes is not None
    assert sorted(ax for ax in join.axes if len(ax) == 3) \
        == [(0, 1, 2), (0, 1, 2)]
    assert sorted(ax for ax in join.axes if len(ax) == 2) \
        == [(0, 1), (0, 2), (1, 2)]


def test_tri_plan_serialization_roundtrip():
    """axes annotations survive to_json/from_json (format v5), for both
    CutJoin and LocalCount nodes."""
    g = erdos_renyi(18, 7.0, seed=3)
    cp = compiler.compile((SIX_CYCLE,), g, cache=False, local=True)
    rt = Plan.from_dict(cp.plan.to_dict())
    assert rt == cp.plan
    joins = [n for n in rt.nodes.values() if isinstance(n, CutJoin)]
    locs = [n for n in rt.nodes.values() if isinstance(n, LocalCount)]
    assert joins and all(isinstance(j.axes, (tuple, type(None)))
                         for j in joins)
    cp2 = lowering.lower(rt, g)
    assert cp2.count(SIX_CYCLE) == cp.count(SIX_CYCLE)
    if locs:
        for loc in locs:
            assert np.array_equal(np.asarray(cp2.value(loc.key)),
                                  np.asarray(cp.value(loc.key)))


# -- the budget story ---------------------------------------------------------------

def _tri_join_node(p, cut, graph_n):
    cand = frontend.decomposed_candidate(p, cut, graph_n=graph_n,
                                         max_cut=3)
    return next(n for n in cand.nodes if isinstance(n, CutJoin))


def test_budget_refuses_3d_factors_but_not_pairs():
    """Σ factor elements > 4·budget prices a 3-D-factor tri join
    infinite; the pair-only form of the same width stays finite under
    the same budget (no unnecessary 3-D tensor is ever the reason a
    3-cut is refused)."""
    from repro.core.apct import APCT
    g = erdos_renyi(24, 4.0, seed=1)
    apct = APCT(g, num_samples=256)
    n_big = 4096                        # pretend-huge graph
    budget = 1 << 27                    # 2 * 4096^3 elems >> 4 * budget
    tri = _tri_join_node(K5_MINUS_EDGE, frozenset({0, 1, 2}), n_big)
    assert costing.node_cost(tri, apct, n_big, budget) == math.inf
    pair = _tri_join_node(SIX_CYCLE, frozenset({0, 2, 4}), n_big)
    assert costing.node_cost(pair, apct, n_big, budget) < math.inf
    # and at a size where the 3-D factors do fit, the tri join prices
    # finite too (512^3 * 2 <= 4 * 2^27)
    assert costing.node_cost(tri, apct, 512, budget) < math.inf


def test_budget_refusal_falls_back_to_narrower_plan():
    """End-to-end: when a pattern's only decomposition needs 3-D
    factors and they exceed the budget, the selection falls back to the
    dense Möbius route — the compiled plan carries no 3-cut join and
    still executes exactly.  budget=128 at n=8: one 8³ contraction
    intermediate fits (512 <= 4·budget) but the tri join's two 8³
    factors plus three 8² pair factors (1216 elements) do not."""
    g = erdos_renyi(8, 4.0, seed=11)
    p = K5_MINUS_EDGE                    # only cutting set has size 3
    cp_small = compiler.compile((p,), g, cache=False, budget=128)
    assert not any(isinstance(n, CutJoin)
                   for n in cp_small.plan.nodes.values())
    assert cp_small.count(p) == brute_force_edge_induced(g, p)
    # same pattern, budget where the 3-D factors fit: the tri plan wins
    cp_big = compiler.compile((p,), g, cache=False, budget=1 << 27)
    assert any(isinstance(n, CutJoin) and n.cut_size == 3
               for n in cp_big.plan.nodes.values())
    assert cp_big.count(p) == cp_small.count(p)
    # chain(5)'s 3-cuts are pair/vector-only formulations: the factor
    # budget must NOT refuse them even at the small budget
    tri = _tri_join_node(chain(5), frozenset({1, 2, 3}), 8)
    assert all(len(ax) <= 2 for ax in tri.axes)


def test_costing_prices_anchored_flat_mobius_finite():
    """The frontier_sizes tightening (actual free-axis participation):
    an anchored flat-Möbius candidate on a large graph must price
    finite — its einsums never materialise a width-3 intermediate."""
    from repro.core.apct import APCT
    g = erdos_renyi(24, 4.0, seed=1)
    apct = APCT(g, num_samples=256)
    cand = frontend.anchored_direct_candidate(chain(5), 0)
    n_huge = 1 << 14                    # n^3 would dwarf any budget
    cost = costing.candidate_cost(cand, apct, n_huge, {}, 1 << 27)
    assert cost < math.inf


def test_anchored_nodes_share_canonical_numbering():
    """Regression: LocalCount node keys embed cut/keep signatures in
    local vertex ids under the canonical pattern_key namespace.  When
    anchored candidates were built on the caller's (non-canonical)
    instance numbering, a 1-cut anchored node could collide with the
    canonical unanchored node — same key, different content — and
    first-wins CSE served one anchor another cut vertex's vector (the
    sums agreed, the entries didn't).  chain(5) is not self-canonical,
    so every anchored vector must still equal ``inj_free`` exactly on a
    graph large enough (n > 128) for the tile floors to steer selection
    toward the colliding 1-cut plan."""
    p = chain(5)
    assert p.canonical().edges != p.edges     # the precondition that bit
    g = erdos_renyi(150, 5.0, seed=0)
    eng = CountingEngine(g)
    for _ in range(2):                        # warm engine shifts choices
        cp = compiler.compile((p,), g, counter=eng, cache=False,
                              local=True)
        for orbit in p.vertex_orbits():
            got = cp.local_counts(p, orbit[0])
            want = eng.inj_free(p, orbit[0])
            assert np.array_equal(got, want), orbit[0]


def test_elimination_widths_thread_free_participation():
    """Free axes enter a step's width only when a factor carries them."""
    from repro.core import homomorphism as H
    p = chain(6)
    order = H.greedy_plan(p, (0,))
    widths = dict(H.elimination_widths(p, order, free=(0,)))
    # interior chain eliminations touch two neighbours at most; the old
    # estimate would report 3 everywhere (frontier + the free axis)
    assert max(widths.values()) == 2
    # K4 with three free axes: the one elimination genuinely joins all
    # three free neighbours
    k4 = clique(4)
    widths = dict(H.elimination_widths(k4, H.greedy_plan(k4, (0, 1, 2)),
                                       free=(0, 1, 2)))
    assert widths == {3: 3}
