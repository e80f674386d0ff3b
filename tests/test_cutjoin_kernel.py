"""CutJoin Pallas kernel tier: primitive oracle tests plus golden-value
equivalence of the kernel path vs the XLA ``_join_reduce`` oracle vs
brute force — across cut sizes 1-2, graphs whose ``n`` is not a tile
multiple, and labelled graphs.  Everything runs in interpret mode (CPU
CI)."""
import numpy as np
import pytest

from repro.compiler import frontend, lowering
from repro.core.counting import CountingEngine, brute_force_edge_induced
from repro.core.decomposition import cutting_sets
from repro.core.pattern import Pattern, chain, cycle, tailed_triangle
from repro.graph.generators import erdos_renyi, triangle_rich
from repro.kernels import ops

HOUSE = Pattern(5, [(0, 1), (1, 2), (2, 3), (3, 0), (0, 4), (1, 4)])
RNG = np.random.default_rng(7)


# -- primitive: prod_reduce vs numpy ----------------------------------------------

@pytest.mark.parametrize("n", [24, 128, 130, 200])
@pytest.mark.parametrize("k", [1, 2, 3])
def test_pair_join_matches_numpy(n, k):
    """Σ [x≠y]·Π F_i[x,y] — in-kernel mask, any n, k factors."""
    Fs = [RNG.integers(0, 9, size=(n, n)).astype(np.float64)
          for _ in range(k)]
    prod = np.prod(np.stack(Fs), axis=0)
    got = ops.cutjoin_reduce(Fs, distinct=True, interpret=True)
    assert got == (prod * (1.0 - np.eye(n))).sum()
    got = ops.cutjoin_reduce(Fs, distinct=False, interpret=True)
    assert got == prod.sum()


@pytest.mark.parametrize("n", [24, 130, 513])
@pytest.mark.parametrize("k", [1, 2, 3])
def test_vector_join_matches_numpy(n, k):
    """|cut| = 1 fast path: Σ_x Π F_i[x]."""
    vs = [RNG.integers(0, 9, size=(n,)).astype(np.float64)
          for _ in range(k)]
    got = ops.cutjoin_reduce(vs, interpret=True)
    assert got == np.prod(np.stack(vs), axis=0).sum()


def test_pair_join_never_needs_tile_multiple():
    """Regression: arbitrary n works via zero-padding (count-preserving:
    padded factor entries are zero)."""
    for n in (127, 129, 250):
        F = RNG.integers(0, 9, size=(n, n)).astype(np.float64)
        got = ops.cutjoin_reduce([F, F], distinct=True, interpret=True)
        assert got == ((F * F) * (1.0 - np.eye(n))).sum()


@pytest.mark.parametrize("block", [8, 32])
@pytest.mark.parametrize("op", ["pair", "vec", "keep0", "keep1"])
def test_certified_block_below_lane_width(op, block):
    """A certified chunk below 128 runs inside the TPU's 128-lane tiles
    and still bounds every f32 partial: factor products sit just under
    2^24 / block, where one partial per 128 cells would round."""
    m = int((2 ** 24 // block) ** 0.5)        # max |F|: m² · block <= 2^24
    n = 40_000 if op == "vec" else 200
    shape = (n,) if op == "vec" else (n, n)
    Fs = [RNG.integers(0, m + 1, size=shape) for _ in range(2)]
    for F in Fs:
        F.flat[0] = m
    assert ops.cutjoin_exact_block(Fs, interpret=False) == block
    prod = Fs[0] * Fs[1]                      # exact int64 reference
    if op != "vec":
        np.fill_diagonal(prod, 0)
    kw = dict(block=block, tile=128, interpret=True)
    if op.startswith("keep"):
        keep = int(op[-1])
        got = ops.cutjoin_reduce_keep(Fs, keep=keep, **kw)
        assert np.array_equal(got, prod.sum(axis=1 - keep))
    else:
        assert ops.cutjoin_reduce(Fs, **kw) == prod.sum()


# -- golden-value equivalence through the compiler --------------------------------

CUT_PATTERNS = [chain(4), cycle(4), tailed_triangle(), HOUSE, chain(5)]


def _decomposed_counts(p, cut, g, eng):
    """(kernel count, XLA-oracle count) for one decomposed candidate, or
    None when the cut is ineligible."""
    cand = frontend.decomposed_candidate(p, cut, graph_n=g.n)
    if cand is None:
        return None
    plan = frontend.assemble([(p, cand)])
    kern = lowering.lower(plan, g, counter=eng, cutjoin_kernel=True)
    xla = lowering.lower(plan, g, counter=eng, cutjoin_kernel=False)
    return kern.count(p), xla.count(p)


@pytest.mark.parametrize("p", CUT_PATTERNS)
def test_kernel_matches_xla_and_brute_force(p):
    """Every decomposed candidate: kernel == _join_reduce bit-for-bit,
    both == brute force, across cut sizes 1-2."""
    g = erdos_renyi(24, 4.0, seed=1)
    eng = CountingEngine(g)
    want = brute_force_edge_induced(g, p)
    sizes = set()
    for cut in cutting_sets(p):
        got = _decomposed_counts(p, cut, g, eng)
        if got is None:
            continue
        kern, xla = got
        sizes.add(len(cut))
        assert kern == xla, (p, sorted(cut))          # bit-for-bit
        assert kern == want, (p, sorted(cut))
    assert sizes                                      # at least one cut ran


def test_kernel_covers_both_cut_sizes():
    """The sweep above must exercise |cut| = 1 and |cut| = 2 joins."""
    sizes = set()
    for p in CUT_PATTERNS:
        for cut in cutting_sets(p):
            if frontend.decomposed_candidate(p, cut, graph_n=24) is not None:
                sizes.add(len(cut))
    assert {1, 2} <= sizes


@pytest.mark.parametrize("g", [erdos_renyi(130, 4.0, seed=9),
                               triangle_rich(135, 5, seed=3)])
def test_kernel_non_tile_multiple_graph(g):
    """n deliberately not a multiple of the 128 tile: zero-padding keeps
    counts exact and the kernel still matches the XLA oracle."""
    eng = CountingEngine(g)
    for p in (cycle(4), tailed_triangle()):
        for cut in cutting_sets(p):
            got = _decomposed_counts(p, cut, g, eng)
            if got is None:
                continue
            kern, xla = got
            assert kern == xla, (g.n, p, sorted(cut))
            assert abs(kern - eng.edge_induced(p)) < 1e-6


def test_kernel_labelled_graph():
    """Vertex labels on the *graph* don't disturb the (unlabelled-
    pattern) decomposed path: cut tensors are label-free."""
    g = erdos_renyi(40, 4.0, seed=5, num_labels=3)
    assert g.labels is not None
    eng = CountingEngine(g)
    for p in (cycle(4), tailed_triangle()):
        want = brute_force_edge_induced(g, p)
        for cut in cutting_sets(p):
            got = _decomposed_counts(p, cut, g, eng)
            if got is None:
                continue
            kern, xla = got
            assert kern == xla == want, (p, sorted(cut))


# -- costing: materialised free-hom tensors are free ------------------------------

def test_costing_zero_costs_materialised_free_homs():
    from repro.compiler import costing
    from repro.compiler.ir import Contract
    from repro.core.apct import APCT
    g = erdos_renyi(24, 4.0, seed=1)
    eng = CountingEngine(g)
    apct = APCT(g, num_samples=512)
    cand = frontend.decomposed_candidate(cycle(4), frozenset({0, 2}),
                                         graph_n=g.n)
    node = next(n for n in cand.nodes
                if isinstance(n, Contract) and n.free)
    cold = costing.node_cost(node, apct, g.n, counter=eng)
    assert cold > 0.0
    skel = Pattern(node.pattern.n, node.pattern.edges)
    eng.hom_free_tensor(skel, node.free, order=node.order)
    assert costing.node_cost(node, apct, g.n, counter=eng) == 0.0
    # without the engine threaded in, the memo is invisible
    assert costing.node_cost(node, apct, g.n) == cold


def test_matreduce_direct_call_pads():
    """The raw kernel wrapper itself pads (it used to assert on shape)."""
    from repro.kernels.matreduce import matreduce
    from repro.kernels import ref
    rng = np.random.default_rng(3)
    lhs = rng.normal(size=(200, 70)).astype(np.float32)
    rhs = rng.normal(size=(130, 70)).astype(np.float32)
    mask = (rng.random((200, 130)) < 0.4).astype(np.float32)
    got = float(matreduce(lhs, rhs, mask, interpret=True))
    want = float(ref.matreduce_ref(lhs, rhs, mask))
    assert abs(got - want) < abs(want) * 3e-2 + 1.0
