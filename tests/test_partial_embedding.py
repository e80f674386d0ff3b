"""Partial-embedding API: differential/property harness.

Ground truth is brute-force enumeration bucketed by cut assignment; every
invariant is checked with *integer equality* — local counts are exact
counts, not approximations:

  * the full local tensor equals the bucketed enumeration entrywise, for
    every eligible cutting set, across unlabelled and labelled patterns
    and every graph generator;
  * anchored local counts sum to the global injective count, and equal
    the engine's ``inj_free`` domain vectors entrywise;
  * Σ_v vertex_counts(v) == n_p · inj(p) / |Aut| (each embedding counted
    once per pattern position, orbit-weighted);
  * the |cut| <= 2 keep-axis Pallas kernel agrees bit-for-bit with the
    f64 XLA fallback (both exact integers under the chunk guard);
  * local counts are invariant under graph vertex relabelling
    (hypothesis property, derandomized in CI via conftest profiles).

Plus golden IR locks for ``LocalCount`` plans and the plan-format-v5
drift tests (v3 entries miss cleanly — no strip-and-serve).
"""
import numpy as np
import pytest

from repro import compiler
from repro.api import exists, local_counts, pattern_domains, vertex_counts
from repro.compiler import frontend, lowering
from repro.compiler.cache import PlanCache, plan_key
from repro.compiler.ir import (LocalCount, MobiusCombine,
                               PLAN_FORMAT_VERSION, Plan, local_key,
                               pattern_key)
from repro.core.counting import CountingEngine, brute_force_edge_induced
from repro.core.decomposition import cutting_sets
from repro.core.engine import MiningEngine
from repro.core.fsm import mini_support, mini_support_dense
from repro.core.pattern import (Pattern, chain, clique, cycle,
                                pseudo_clique, star, tailed_triangle)
from repro.graph.generators import (erdos_renyi, rmat, small_world,
                                    triangle_rich)
from repro.graph.storage import Graph

HOUSE = Pattern(5, [(0, 1), (1, 2), (2, 3), (3, 0), (0, 4), (1, 4)])

GRAPHS = {
    "er": erdos_renyi(20, 4.0, seed=1),
    "rmat": rmat(4, 5.0, seed=2),
    "ws": small_world(22, 4, 0.2, seed=3),
    "tri": triangle_rich(24, 4, seed=4),
    "tri-lab": triangle_rich(24, 4, seed=5, num_labels=2),
}

PATTERNS = [chain(4), cycle(4), tailed_triangle(), star(4), HOUSE]
LABELLED = [Pattern(3, [(0, 1), (1, 2)], (0, 1, 0)),
            Pattern(4, [(0, 1), (1, 2), (0, 2), (2, 3)], (0, 1, 0, 1)),
            Pattern(4, [(0, 1), (1, 2), (2, 3)], (1, 0, 0, 1))]

_ENGINES = {}


def eng_for(gname):
    if gname not in _ENGINES:
        _ENGINES[gname] = CountingEngine(GRAPHS[gname])
    return _ENGINES[gname]


def brute_local(g, p, cut):
    """Oracle: injective embedding tuples bucketed by cut assignment."""
    m = MiningEngine.__new__(MiningEngine)      # enumeration only
    m.graph = g
    cut_list = sorted(cut)
    out = np.zeros((g.n,) * len(cut_list))
    for emb in MiningEngine._enumerate(m, p):
        out[tuple(emb[c] for c in cut_list)] += 1
    return out


# -- the core differential: local tensor == bucketed enumeration ------------------

@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_local_tensor_matches_enumeration(gname):
    """Every eligible cutting set of every pattern: the reduce-free
    local tensor equals brute force entrywise, and its sum reproduces
    the global injective count (integer equality)."""
    g = GRAPHS[gname]
    eng = eng_for(gname)
    pats = PATTERNS + (LABELLED if g.labels is not None else [])
    checked = 0
    for p in pats:
        inj = brute_force_edge_induced(g, p) * p.aut_order()
        for cut in cutting_sets(p):
            cand = frontend.local_candidate(p, cut, graph_n=g.n)
            if cand is None:
                continue
            plan = Plan()
            for node in cand.nodes:
                plan.add(node)
            cp = lowering.lower(plan, g, counter=eng)
            L = np.asarray(cp.value(cand.out_key))
            assert np.array_equal(L, brute_local(g, p, cut)), \
                (gname, p, sorted(cut))
            assert L.sum() == inj, (gname, p, sorted(cut))
            checked += 1
    assert checked >= len(pats)


@pytest.mark.parametrize("gname", ["er", "tri-lab"])
@pytest.mark.slow
def test_anchored_sums_to_global_and_matches_domains(gname):
    """Anchored vectors: Σ_u A_v[u] == inj(p) for every anchor v, and
    A_v equals the engine's inj_free domain entrywise — whichever route
    (decomposition join or flat Möbius) the plan took."""
    g = GRAPHS[gname]
    eng = eng_for(gname)
    pats = [chain(4), tailed_triangle(), clique(4)] + \
        (LABELLED[:2] if g.labels is not None else [])
    for p in pats:
        inj = brute_force_edge_induced(g, p) * p.aut_order()
        for v in range(p.n):
            lc = local_counts(p, g, anchor=v, counter=eng, cache=False)
            assert lc.counts.sum() == inj, (gname, p, v, lc.style)
            assert np.array_equal(lc.counts, eng.inj_free(p, v)), \
                (gname, p, v, lc.style)


@pytest.mark.slow
def test_vertex_counts_orbit_invariant():
    """Σ_u vertex_counts[u] == n_p · inj(p) / |Aut|: each edge-induced
    embedding contributes once per pattern position (integer equality
    after the orbit weighting)."""
    g = GRAPHS["er"]
    eng = eng_for("er")
    for p in [chain(4), cycle(4), tailed_triangle(), clique(4), HOUSE]:
        want = p.n * brute_force_edge_induced(g, p)
        vc = vertex_counts(p, g, counter=eng, cache=False)
        assert vc.sum() == want, (p, vc.sum(), want)
        assert np.all(vc >= 0)


def test_vertex_counts_matches_per_vertex_brute_force():
    """vertex_counts[u] == # edge-induced embeddings containing u,
    counted from the raw enumeration."""
    g = GRAPHS["rmat"]
    m = MiningEngine.__new__(MiningEngine)
    m.graph = g
    for p in (tailed_triangle(), cycle(4)):
        per_emb = {}
        for emb in MiningEngine._enumerate(m, p):
            per_emb[tuple(sorted(emb))] = \
                per_emb.get(tuple(sorted(emb)), 0) + 1
        want = np.zeros(g.n)
        for key, c in per_emb.items():
            assert c % p.aut_order() == 0
            for u in key:
                want[u] += c // p.aut_order()
        vc = vertex_counts(p, g, counter=eng_for("rmat"), cache=False)
        assert np.array_equal(vc, want), p


# -- keep-axis kernel: bit-for-bit vs the XLA path ---------------------------------

@pytest.mark.parametrize("n", [24, 100, 150])
@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("keep", [0, 1])
def test_keep_axis_kernel_bitforbit(n, k, keep):
    """cutjoin_reduce_keep == the f64 masked mask-and-sum on integer
    factors, bit-for-bit, across factor counts, non-tile-multiple n,
    and both keep axes."""
    from repro.kernels import ops
    rng = np.random.default_rng(n * 10 + k * 2 + keep)
    Fs = [rng.integers(0, 7, size=(n, n)).astype(np.float64)
          for _ in range(k)]
    assert ops.cutjoin_exact_block(Fs) is not None
    got = ops.cutjoin_reduce_keep(Fs, keep=keep)
    prod = np.ones((n, n))
    for F in Fs:
        prod *= F
    np.fill_diagonal(prod, 0.0)
    want = prod.sum(axis=1 - keep)
    assert got.shape == (n,) and got.dtype == np.float64
    assert np.array_equal(got, want)


def test_keep_axis_kernel_through_lowering_bitforbit():
    """An anchored |cut| = 2 plan evaluated with the kernel tier and
    with ``cutjoin_kernel=False`` (XLA fallback) returns bit-identical
    vectors."""
    g = GRAPHS["ws"]
    p = cycle(5)                          # anchored cuts have size 2
    ck = compiler.compile((p,), g, counter=CountingEngine(g),
                          cache=False, local=True)
    cx = compiler.compile((p,), g, counter=CountingEngine(g),
                          cache=False, local=True, cutjoin_kernel=False)
    key = local_key(p, 0)
    assert ck.plan.meta["local_cuts"][key] is not None
    # anchored cuts of a 5-cycle have size 2; the tri tier may commit a
    # 3-cut when the model prices it cheaper — either way the kernel
    # tier (pair or tri keep-axis) must match the XLA oracle exactly
    assert len(ck.plan.meta["local_cuts"][key]) in (2, 3)
    assert 0 in ck.plan.meta["local_cuts"][key]
    a, b = ck.local_counts(p, 0), cx.local_counts(p, 0)
    assert np.array_equal(a, b)
    assert np.array_equal(a, CountingEngine(g).inj_free(p, 0))


@pytest.mark.parametrize("p", [chain(4), tailed_triangle(), cycle(5)],
                         ids=["chain4", "tailed-tri", "cycle5"])
def test_compiled_anchored_counts_match_direct_route(p):
    """Every orbit's anchored vector read off one ``local=True`` plan
    equals the flat Möbius route's, on a fresh engine."""
    g = GRAPHS["er"]
    cp = compiler.compile((p,), g, counter=CountingEngine(g), cache=False,
                          local=True)
    for orbit in p.vertex_orbits():
        direct = local_counts(p, g, anchor=orbit[0],
                              counter=CountingEngine(g),
                              use_compiler=False).counts
        assert np.array_equal(cp.local_counts(p, orbit[0]), direct), orbit


def test_exact_guard_falls_back_to_xla():
    """Factors beyond the f32 chunk guard must still evaluate exactly
    (the keep-axis path falls through to the f64 XLA join)."""
    from repro.kernels import ops
    n = 40
    big = float(1 << 23)
    Fs = [np.full((n, n), big), np.full((n, n), 4.0)]
    assert ops.cutjoin_exact_block(Fs) is None
    prod = np.full((n, n), big * 4.0)
    np.fill_diagonal(prod, 0.0)
    want = prod.sum(axis=1)
    # lowering-level check: _eval_local takes the fallback
    from repro.compiler.lowering import _join_keep
    import jax
    import jax.numpy as jnp
    with jax.enable_x64():
        got = np.asarray(_join_keep(jnp.stack(
            [jnp.asarray(F) for F in Fs]), 0), np.float64)
    assert np.array_equal(got, want)


# -- existence fast path -----------------------------------------------------------

@pytest.mark.slow
def test_exists_matches_engine():
    g = GRAPHS["er"]
    eng = eng_for("er")
    for p in [chain(4), clique(3), clique(4), clique(6), cycle(5),
              star(5)]:
        assert exists(p, g, counter=eng, cache=False) == \
            eng.existence(p), p


def test_exists_early_exit_skips_join():
    """A graph with no triangles: any pattern containing one dies at its
    triangle factor, before the join or shrinkage corrections — counted
    by the plan's early-exit stat."""
    g = Graph(12, [(i, (i + 1) % 12) for i in range(12)])   # 12-cycle
    p = tailed_triangle()
    cp = compiler.compile((p,), g, cache=False, local=True)
    assert cp.exists(p) is False
    assert cp.stats["exists_early_exits"] == 1
    assert exists(p, g, cache=False) is False
    assert brute_force_edge_induced(g, p) == 0


# -- consumers: FSM MINI support and the pseudo-clique miner -----------------------

def test_mini_support_api_matches_dense():
    """MINI support through anchored local counts == the legacy dense
    inj_free_all route, labelled and unlabelled."""
    eng = eng_for("tri-lab")
    for p in LABELLED + [chain(3)]:
        assert mini_support(eng, p) == mini_support_dense(eng, p), p


def test_pattern_domains_match_inj_free():
    eng = eng_for("tri-lab")
    p = LABELLED[1]
    doms = pattern_domains(eng, p)
    assert set(doms) == {o[0] for o in p.vertex_orbits()}
    for rep, vec in doms.items():
        assert np.array_equal(vec, eng.inj_free(p, rep)), rep


def test_pseudo_clique_miner_differential():
    """Miner per-vertex participation == brute-force enumeration of
    every pseudo-clique pattern, and totals match the engine counts."""
    from repro.core.search import mine_pseudo_cliques
    g = GRAPHS["er"]
    eng = eng_for("er")
    r = mine_pseudo_cliques(g, 4, missing=1, counter=eng,
                            use_compiler=False)
    m = MiningEngine.__new__(MiningEngine)
    m.graph = g
    want = np.zeros(g.n)
    tot = {}
    for p in pseudo_clique(4, 1):
        cnt = {}
        for emb in MiningEngine._enumerate(m, p):
            cnt[tuple(sorted(emb))] = cnt.get(tuple(sorted(emb)), 0) + 1
        tot[p] = 0
        for key, c in cnt.items():
            tot[p] += c // p.aut_order()
            for u in key:
                want[u] += c // p.aut_order()
    assert np.array_equal(r.per_vertex, want)
    for p, v in r.totals.items():
        assert v == tot[p.canonical()], p
    assert r.hotspots == sorted(
        (u for u in range(g.n) if want[u] >= 1),
        key=lambda u: (-want[u], u))


# -- serving -----------------------------------------------------------------------

def test_batcher_serves_local_requests():
    from repro.serve.batching import PatternQueryBatcher, PatternRequest
    g = GRAPHS["tri"]
    eng = eng_for("tri")
    b = PatternQueryBatcher(g, max_batch=4)
    pats = (chain(4), tailed_triangle())
    for i in range(4):
        b.submit(PatternRequest(uid=i, patterns=pats, local=True,
                                anchor=(0 if i % 2 else None)))
    b.run_to_completion()
    assert len(b.finished) == 4
    assert b.stats["compiles"] == 1                # one local plan
    for req in b.finished:
        assert req.done and not req.error
        for p in pats:
            arr = req.local_counts[p]
            inj = brute_force_edge_induced(g, p) * p.aut_order()
            assert arr is not None and arr.sum() == inj
            if req.anchor is not None:
                assert np.array_equal(arr, eng.inj_free(p, req.anchor))


def test_batcher_local_fallback_on_compile_failure(monkeypatch):
    from repro import compiler as compiler_mod
    from repro.serve.batching import PatternQueryBatcher, PatternRequest

    def boom(*a, **k):
        raise RuntimeError("compiler down")

    g = GRAPHS["tri"]
    monkeypatch.setattr(compiler_mod, "compile", boom)
    b = PatternQueryBatcher(g, max_batch=2)
    b.submit(PatternRequest(uid=0, patterns=(chain(4), clique(4)),
                            local=True, anchor=0))
    b.run_to_completion()
    req = b.finished[0]
    assert req.done and not req.error and b.stats["fallbacks"] == 1
    eng = eng_for("tri")
    for p in (chain(4), clique(4)):
        assert np.array_equal(req.local_counts[p], eng.inj_free(p, 0))


# -- golden IR locks ---------------------------------------------------------------

def test_golden_local_plan_tailed_triangle():
    """Tailed triangle, cut {2}: a LocalCount over one kept axis with
    two factors (triangle + edge) and a nonempty anchored shrinkage
    correction; the anchored-at-2 output aliases the same node."""
    p = tailed_triangle()
    cand = frontend.local_candidate(p, frozenset({2}), graph_n=24)
    assert cand is not None and cand.style == "local"
    out = cand.nodes[-1]
    assert isinstance(out, LocalCount)
    assert out.cut_size == 1 and out.keep == (0,)
    assert len(out.factors) == 2                   # one M_i per subpattern
    assert len(out.corrections) >= 1               # triangle shrinkage
    for _, ref in out.corrections:
        assert ref.startswith("homf:")
    # anchored at the cut vertex: same join, same keep
    canda = frontend.local_candidate(p, frozenset({2}), graph_n=24,
                                     anchor=2)
    assert canda.nodes[-1].key == out.key


def test_golden_local_plan_keep_axes():
    """4-chain, cut {1, 2}: the reduce-free tensor keeps both axes;
    anchoring vertex 1 keeps only axis 0."""
    p = chain(4)
    cut = frozenset({1, 2})
    full = frontend.local_candidate(p, cut, graph_n=24)
    anch = frontend.local_candidate(p, cut, graph_n=24, anchor=1)
    nf, na = full.nodes[-1], anch.nodes[-1]
    assert nf.cut_size == na.cut_size == 2
    assert nf.keep == (0, 1) and na.keep == (0,)
    assert nf.factors == na.factors                # same join, new output
    assert nf.key != na.key


def test_golden_anchored_direct_candidate():
    """Cliques have no cutting set: the anchored fallback is one flat
    Möbius combine over single-free-vertex hom tensors."""
    cand = frontend.anchored_direct_candidate(clique(4), 0)
    out = cand.nodes[-1]
    assert isinstance(out, MobiusCombine) and out.divisor == 1
    assert cand.style == "local-direct"
    assert all(ref.startswith("homf:") for _, ref in out.terms)


def test_local_key_orbit_and_isomorph_stable():
    """local_key collapses automorphism-orbit anchors and isomorphic
    renumberings; anchored and unanchored namespaces never collide even
    when marker labels mimic real labels."""
    p = chain(4)
    assert local_key(p, 0) == local_key(p, 3)      # end vertices: one orbit
    assert local_key(p, 1) == local_key(p, 2)
    assert local_key(p, 0) != local_key(p, 1)
    q = Pattern(4, [(3, 2), (2, 1), (1, 0)])       # same chain renumbered
    assert local_key(q, 3) == local_key(p, 0)
    lab = Pattern(3, [(0, 1), (1, 2)], (0, 0, 1))
    assert local_key(lab) != local_key(chain(3), 2)


# -- plan cache: format v5, no strip-and-serve -------------------------------------

def test_plan_format_v5_drift(tmp_path):
    """v4 (or any non-v5) on-disk entries miss cleanly: a pre-axis-subset
    reader version must never be half-loaded with |cut| = 3 factors
    expanded over the full cut (nor a pre-LocalCount one with local
    outputs stripped)."""
    import json
    g = GRAPHS["er"]
    cache = PlanCache(str(tmp_path))
    pats = (chain(4),)
    key = plan_key(pats, g)
    cp = compiler.compile(pats, g, cache=cache, local=True)
    assert cp.plan.to_dict()["version"] == PLAN_FORMAT_VERSION == 5
    d = json.loads(open(cache._file(key)).read())
    assert any(nd["op"] == "local" for nd in d["nodes"])
    for stale in (4, 3, 1, None):
        d2 = dict(d)
        if stale is None:
            d2.pop("version", None)
        else:
            d2["version"] = stale
        with open(cache._file(key), "w") as fh:
            fh.write(json.dumps(d2))
        fresh = PlanCache(str(tmp_path))
        assert fresh.get(key) is None, stale
    with pytest.raises(ValueError):
        Plan.from_dict({"version": 3, "nodes": [], "outputs": {}})


def test_local_cache_interplay_no_strip_and_serve():
    """A cached plan without local outputs misses a local=True request
    (recompile, never served stripped); the richer local plan then
    serves count-only lookups from cache."""
    g = GRAPHS["er"]
    cache = PlanCache()
    pats = (chain(4),)
    cp1 = compiler.compile(pats, g, cache=cache)
    assert not cp1.plan.meta["local"]
    cp2 = compiler.compile(pats, g, cache=cache, local=True)
    assert not cp2.from_cache                  # no local outputs: recompile
    assert cp2.has_local(pats[0]) and cp2.has_local(pats[0], 0)
    cp3 = compiler.compile(pats, g, cache=cache)
    assert cp3.from_cache                      # superset plan serves counts
    cp4 = compiler.compile(pats, g, cache=cache, local=True)
    assert cp4.from_cache
    assert np.array_equal(cp4.local_counts(pats[0]),
                          cp2.local_counts(pats[0]))
    assert cp4.count(pats[0]) == cp1.count(pats[0])


def test_unanchored_tensor_canonical_across_renumberings():
    """The unanchored output key collapses isomorphic renumberings, so
    the tensor must be expressed in canonical-form numbering: a caller
    holding a different renumbering gets the same well-defined answer
    (axes name canonical vertices), never a tensor whose axes silently
    refer to someone else's numbering."""
    g = GRAPHS["er"]
    cache = PlanCache()
    p = chain(4)                                   # path 0-1-2-3
    q = Pattern(4, [(0, 2), (0, 3), (3, 1)])       # same path renumbered
    assert pattern_key(p) == pattern_key(q)
    lc_p = local_counts(p, g, cache=cache)         # compiles
    lc_q = local_counts(q, g, cache=cache)         # cache hit, same entry
    assert lc_q.from_cache
    assert lc_p.axes == lc_q.axes
    assert np.array_equal(lc_p.counts, lc_q.counts)
    # the axes are a genuine cutting set of the canonical form, and the
    # tensor matches brute force on that form
    pc = p.canonical()
    assert frozenset(lc_p.axes) in set(cutting_sets(pc))
    assert np.array_equal(lc_p.counts,
                          brute_local(g, pc, frozenset(lc_p.axes)))
    # uncompiled direct path: same canonical semantics
    lc_d = local_counts(q, g, use_compiler=False)
    assert np.array_equal(lc_d.counts,
                          brute_local(g, pc, frozenset(lc_d.axes)))


def test_anchored_axes_name_the_anchor():
    g = GRAPHS["er"]
    lc = local_counts(chain(4), g, anchor=2, cache=False)
    assert lc.axes == (2,) and lc.counts.shape == (g.n,)


def test_domains_local_union_no_cache_ping_pong():
    """Alternating domains=True and local=True requests for one pattern
    set must not evict each other: the recompile unions the stored
    plan's flags, so the third request (and everything after) hits."""
    g = GRAPHS["tri-lab"]
    pats = (LABELLED[0],)
    cache = PlanCache()
    cp1 = compiler.compile(pats, g, cache=cache, domains=True)
    cp2 = compiler.compile(pats, g, cache=cache, local=True)
    assert not cp2.from_cache                  # first local: recompile...
    assert cp2.plan.meta["domains"] and cp2.plan.meta["local"]  # ...union
    cp3 = compiler.compile(pats, g, cache=cache, domains=True)
    cp4 = compiler.compile(pats, g, cache=cache, local=True)
    assert cp3.from_cache and cp4.from_cache   # both flavors now hit
    assert cp3.mini_support(pats[0]) == cp1.mini_support(pats[0])


def test_local_counts_returns_a_copy():
    """Served arrays must not alias the plan's node-value memo: an
    in-place edit by one caller must not corrupt later answers."""
    g = GRAPHS["er"]
    p = chain(4)
    cp = compiler.compile((p,), g, cache=False, local=True)
    a = cp.local_counts(p, 0)
    a *= 0.0                                   # hostile caller
    b = cp.local_counts(p, 0)
    assert np.array_equal(b, CountingEngine(g).inj_free(p, 0))
    assert not np.array_equal(a, b)


def test_local_roundtrip_executes_identically():
    g = GRAPHS["tri"]
    pats = (chain(4), tailed_triangle())
    cp = compiler.compile(pats, g, cache=False, local=True)
    rt = Plan.from_json(cp.plan.to_json())
    assert rt == cp.plan
    cp2 = lowering.lower(rt, g)
    for p in pats:
        assert np.array_equal(cp2.local_counts(p), cp.local_counts(p))
        for orbit in p.vertex_orbits():
            assert np.array_equal(cp2.local_counts(p, orbit[0]),
                                  cp.local_counts(p, orbit[0]))


# -- hypothesis: relabelling invariance --------------------------------------------

def test_local_counts_invariant_under_relabelling():
    """Property: permuting graph vertices permutes anchored local-count
    vectors (and vertex_counts) by the same permutation — the counts
    are a graph invariant, not an artifact of vertex order.  Runs
    derandomized under the CI profile (see conftest)."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    pats = [chain(4), tailed_triangle(), cycle(4)]

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000), pi=st.integers(0, 2),
           perm_seed=st.integers(0, 10_000))
    def check(seed, pi, perm_seed):
        g = erdos_renyi(14, 3.0, seed=seed)
        p = pats[pi]
        perm = np.random.default_rng(perm_seed).permutation(g.n)
        g2 = Graph(g.n, np.stack([perm[g.edges[:, 0]],
                                  perm[g.edges[:, 1]]], 1))
        e1, e2 = CountingEngine(g), CountingEngine(g2)
        for v in (0, p.n - 1):
            a = local_counts(p, g, anchor=v, counter=e1,
                             use_compiler=False).counts
            b = local_counts(p, g2, anchor=v, counter=e2,
                             use_compiler=False).counts
            assert np.array_equal(b[perm], a), (seed, pi, v)
        va = vertex_counts(p, g, counter=e1, use_compiler=False)
        vb = vertex_counts(p, g2, counter=e2, use_compiler=False)
        assert np.array_equal(vb[perm], va)

    check()


def test_labelled_local_counts_invariant_under_relabelling():
    """Same property on a labelled graph: labels travel with their
    vertices under the permutation."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    p = Pattern(4, [(0, 1), (1, 2), (0, 2), (2, 3)], (0, 1, 0, 1))

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 10_000), perm_seed=st.integers(0, 10_000))
    def check(seed, perm_seed):
        g = erdos_renyi(14, 3.5, seed=seed, num_labels=2)
        perm = np.random.default_rng(perm_seed).permutation(g.n)
        labels2 = np.empty(g.n, g.labels.dtype)
        labels2[perm] = g.labels
        g2 = Graph(g.n, np.stack([perm[g.edges[:, 0]],
                                  perm[g.edges[:, 1]]], 1), labels2)
        a = local_counts(p, g, anchor=3, counter=CountingEngine(g),
                         use_compiler=False).counts
        b = local_counts(p, g2, anchor=3, counter=CountingEngine(g2),
                         use_compiler=False).counts
        assert np.array_equal(b[perm], a), (seed, perm_seed)

    check()
