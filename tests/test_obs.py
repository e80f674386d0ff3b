"""Observability layer: metrics registry / StatsView semantics, the
plan-execution tracer (golden trace shape, span-nesting-matches-IR,
coverage, exports), cost-model drift aggregation, per-phase batcher
fallback accounting, and PlanCache eviction metrics."""
import json

import numpy as np
import pytest

from repro import compiler, obs
from repro.obs.drift import (aggregate, group_key, pairs_from_trace,
                             spearman)
from repro.obs.metrics import MetricsRegistry, StatsView
from repro.core.counting import CountingEngine
from repro.core.pattern import Pattern, chain, clique, cycle
from repro.graph.generators import erdos_renyi

K5_MINUS_EDGE = Pattern(5, [(u, v) for u in range(5)
                            for v in range(u + 1, 5) if (u, v) != (3, 4)])

G = erdos_renyi(24, 4.0, seed=1)


def _traced(p, g=G, *, cutjoin_kernel=True, local=False):
    tr = obs.Tracer()
    cp = compiler.compile(p, g, counter=CountingEngine(g), cache=False,
                          cutjoin_kernel=cutjoin_kernel, local=local)
    cp.tracer = tr
    cp.count(p)
    return tr, cp


# -- metrics registry --------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    r = MetricsRegistry()
    assert r.counter("c") == 1
    assert r.counter("c", 4) == 5
    assert r.get("c") == 5
    r.gauge("g", 2.5)
    r.gauge("g", 7.0)                       # gauges overwrite
    assert r.get("g") == 7.0
    for v in (1.0, 3.0, 2.0):
        r.observe("h", v)
    h = r.get("h")
    assert h == {"count": 3, "sum": 6.0, "min": 1.0, "max": 3.0,
                 "mean": 2.0, "last": 2.0}
    assert r.get("absent", default=None) is None


def test_registry_labels_separate_series():
    r = MetricsRegistry()
    r.counter("k", cut=2)
    r.counter("k", 2, cut=3)
    assert r.get("k", cut=2) == 1
    assert r.get("k", cut=3) == 2
    assert r.get("k") == 0.0                # unlabelled series untouched
    assert r.series("k") == {(("cut", 2),): 1.0, (("cut", 3),): 2.0}
    snap = r.snapshot()
    assert snap["k"] == {"cut=2": 1.0, "cut=3": 2.0}
    json.loads(r.dump())                    # serialisable
    r.reset()
    assert r.snapshot() == {}


def test_stats_view_local_reads_registry_mirror():
    r = MetricsRegistry()
    v = StatsView("pfx", keys=("a", "b"), registry=r, tier="x")
    assert v["a"] == 0 and dict(v) == {"a": 0, "b": 0}
    v["a"] += 1
    v["a"] += 2
    assert v["a"] == 3 and isinstance(v["a"], int)
    assert r.get("pfx.a", tier="x") == 3
    # equality with plain dicts: the contract the old ad-hoc dicts gave
    assert v == {"a": 3, "b": 0}
    # a local reset never decrements the registry (monotonic counters)
    v["a"] = 0
    assert v["a"] == 0
    assert r.get("pfx.a", tier="x") == 3
    v["a"] += 1
    assert v["a"] == 1 and r.get("pfx.a", tier="x") == 4


# -- tracer ------------------------------------------------------------------------

def test_golden_trace_shape_3cut():
    """Trace-shape lock on the K5-minus-edge tri-join plan: the span
    tree mirrors the evaluation recursion — one execute root, the
    ShrinkageCorrect output under it, the CutJoin (kernel route, guard
    granted) with its factor Contracts beneath, and the correction's
    Möbius/Intersect chain — and memo hits open no spans."""
    tr, cp = _traced(K5_MINUS_EDGE)
    assert len(tr.roots) == 1
    root = tr.roots[0]
    assert root.kind == "execute" and root.attrs["op"] == "count"
    (shrink,) = root.children
    assert shrink.kind == "ShrinkageCorrect"
    assert shrink.attrs["route"] == "host"
    kinds = [c.kind for c in shrink.children]
    assert kinds == ["CutJoin", "MobiusCombine"]
    join, mob = shrink.children
    assert join.attrs["cut_size"] == 3
    assert join.attrs["route"] == "kernel"
    assert join.attrs["exact_block"] is not None
    assert join.attrs["predicted"] is not None
    shapes = join.attrs["factor_shapes"]
    assert shapes and all(all(d == G.n for d in s) for s in shapes)
    assert all(c.kind == "Contract" for c in join.children)
    assert all(c.attrs["route"] == "einsum-free" for c in join.children)
    assert [c.kind for c in mob.children] == ["Intersect"]
    assert mob.children[0].attrs["route"] == "clique-device"
    # second read: everything memoised, no new spans
    n_before = sum(1 for _ in tr.walk())
    cp.count(K5_MINUS_EDGE)
    assert sum(1 for _ in tr.walk()) == n_before + 1    # just the root


@pytest.fixture(scope="module")
def clique_plan():
    """One plan counting K3, K4 and K5, traced."""
    g = erdos_renyi(20, 9.0, seed=2)
    tr = obs.Tracer()
    pats = [clique(3), clique(4), clique(5)]
    cp = compiler.compile(pats, g, counter=CountingEngine(g), cache=False)
    cp.tracer = tr
    for p in pats:
        cp.count(p)
    return g, tr, cp


@pytest.mark.parametrize("k,route", [(3, "clique-device"),
                                     (4, "clique-device"),
                                     (5, "enumeration")])
def test_intersect_route_follows_clique_size(clique_plan, k, route):
    """K3 and K4 Intersect nodes take the device pass; a larger clique
    is still counted by host enumeration.  Either way the node's value
    is k! times the enumerated cliques."""
    import math
    from repro.core.cliques import clique_count
    g, tr, cp = clique_plan
    (node,) = [s for s in tr.walk() if s.kind == "Intersect"
               and cp.plan.nodes[s.name].k == k]
    assert node.attrs["route"] == route
    assert cp.value(node.name) == math.factorial(k) * clique_count(g, k)


def test_trace_route_xla_dense_when_kernel_off():
    tr, cp = _traced(K5_MINUS_EDGE, cutjoin_kernel=False)
    joins = [s for s in tr.walk() if s.kind == "CutJoin"]
    assert joins and all(s.attrs["route"] == "xla-dense" for s in joins)
    tk, ck = _traced(K5_MINUS_EDGE, cutjoin_kernel=True)
    assert cp.count(K5_MINUS_EDGE) == ck.count(K5_MINUS_EDGE)


def test_trace_coverage_and_self_time():
    tr, cp = _traced(K5_MINUS_EDGE)
    cov = tr.coverage()
    assert cov is not None and 0.95 <= cov <= 1.0 + 1e-9
    for s in tr.walk():
        child_total = sum(c.duration_s for c in s.children)
        assert s.duration_s >= 0.0
        assert abs(s.self_s - max(0.0, s.duration_s - child_total)) < 1e-12


def test_span_nesting_matches_ir_structure():
    """Property: the trace tree is a subtree of the plan DAG — every
    node span's children are refs of that node, and every root's single
    child is the read's output node.  Randomised over patterns via
    hypothesis when available."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    pats = [cycle(4), chain(4), K5_MINUS_EDGE, cycle(5)]

    @settings(max_examples=4, deadline=None)
    @given(st.integers(0, len(pats) - 1), st.booleans())
    def check(i, kernel):
        p = pats[i]
        tr, cp = _traced(p, cutjoin_kernel=kernel)
        for s in tr.walk():
            if s.kind == "execute":
                assert len(s.children) <= 1
                continue
            if s.kind == "guard-scan":
                # the exact_block factor scan, nested under its join —
                # not an IR node, and it evaluates nothing
                assert not s.children
                continue
            node = cp.plan.nodes[s.name]
            assert type(node).__name__ == s.kind
            refs = set(node.refs())
            for c in s.children:
                if c.kind == "guard-scan":
                    continue
                assert c.name in refs, (s.name, c.name, refs)

    check()


def test_tracer_annotate_and_error_attr():
    tr = obs.Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            tr.annotate(x=1)
            raise ValueError("nope")
    assert tr.roots[0].attrs == {"x": 1, "error": "ValueError"}
    tr.annotate(y=2)                        # outside any span: no-op
    assert "y" not in tr.roots[0].attrs


def test_trace_exports(tmp_path):
    tr, cp = _traced(K5_MINUS_EDGE)
    d = tr.to_dict()
    assert d["meta"]["backend"] and d["coverage"] is not None
    assert d["spans"][0]["kind"] == "execute"
    assert d["spans"][0]["children"][0]["dur_us"] >= 0
    json.loads(tr.to_json())

    # the JSON tree holds every span once, with its timing and attrs
    def flat(spans):
        for s in spans:
            yield s
            yield from flat(s["children"])
    events = list(flat(d["spans"]))
    n_spans = sum(1 for _ in tr.walk())
    assert len(events) == n_spans
    assert all(e["dur_us"] >= 0 and e["start_us"] >= 0
               and 0 <= e["self_us"] <= e["dur_us"] + 1e-6 for e in events)
    assert [e["name"] for e in events] == [s.name for s in tr.walk()]
    # attrs stay JSON-primitive through the export (lists stay lists)
    shapes = [e["attrs"]["factor_shapes"] for e in events
              if "factor_shapes" in e["attrs"]]
    assert shapes and all(isinstance(s, list) for s in shapes)
    # the counts of the tracer's own reads ride along
    assert d["counts"]["transfer.d2h_bytes"]

    p1 = tr.save(str(tmp_path / "t.json"))
    assert p1 == str(tmp_path / "t.json")
    saved = json.load(open(p1))
    assert "spans" in saved and saved == json.loads(tr.to_json())


def test_untraced_plan_opens_no_spans():
    cp = compiler.compile(cycle(4), G, counter=CountingEngine(G),
                          cache=False)
    assert cp.tracer is None
    cp.count(cycle(4))                      # must not touch any tracer


# -- predicted costs on the plan ---------------------------------------------------

def test_plan_meta_node_costs():
    """Compilation records finite per-node APCT predictions for the
    committed nodes, keyed into plan.nodes — the predicted side of the
    drift pairs."""
    cp = compiler.compile(K5_MINUS_EDGE, G, counter=CountingEngine(G),
                          cache=False, local=True)
    costs = cp.plan.meta["node_costs"]
    assert costs
    for k, v in costs.items():
        assert k in cp.plan.nodes
        assert np.isfinite(v) and v >= 0.0
    # every node the count evaluation touches carries a prediction
    tr = obs.Tracer()
    cp.tracer = tr
    cp._values.clear()
    cp.count(K5_MINUS_EDGE)
    for s in tr.walk():
        if s.kind != "execute":
            assert s.attrs["predicted"] is not None, s.name


# -- drift accounting --------------------------------------------------------------

def test_spearman():
    assert spearman([1, 2, 3], [10, 20, 30]) == pytest.approx(1.0)
    assert spearman([1, 2, 3], [30, 20, 10]) == pytest.approx(-1.0)
    assert spearman([1, 2, 3, 4], [1, 3, 2, 4]) == pytest.approx(0.8)
    assert spearman([1, 1, 2], [1, 2, 3]) is not None   # ties averaged
    assert spearman([1], [2]) is None       # too few pairs
    assert spearman([1, 1], [2, 3]) is None  # degenerate side
    assert spearman([1, 2], [2, 3, 4]) is None  # length mismatch


def test_drift_pairs_and_aggregate():
    tr, cp = _traced(K5_MINUS_EDGE)
    pairs = pairs_from_trace(tr.to_dict())
    assert pairs
    keys = {group_key(p) for p in pairs}
    assert "CutJoin|cut=3|kernel" in keys
    assert any(k.startswith("Contract|") for k in keys)
    for p in pairs:
        assert p["predicted"] is not None and p["measured_us"] >= 0.0
        assert p["cls"] in obs.drift.NODE_KINDS

    report = aggregate(pairs)
    assert report["n_pairs"] == len(pairs)
    assert set(report["groups"]) == keys
    for g in report["groups"].values():
        assert g["n"] >= 1 and g["predicted_sum"] >= 0.0
    # rendering and the bench summary never throw on real reports
    text = obs.drift.render(report)
    assert "CutJoin|cut=3|kernel" in text
    summary = obs.drift.bench_summary(report)
    assert set(summary) == keys


def test_drift_aggregate_synthetic():
    """Known pairs → known report: spread = max/min ratio per group."""
    pairs = [
        {"cls": "Contract", "cut": None, "route": "einsum",
         "backend": "cpu", "predicted": 1.0, "measured_us": 10.0},
        {"cls": "Contract", "cut": None, "route": "einsum",
         "backend": "cpu", "predicted": 2.0, "measured_us": 40.0},
        {"cls": "CutJoin", "cut": 2, "route": "kernel",
         "backend": "cpu", "predicted": 5.0, "measured_us": 5.0},
    ]
    r = aggregate(pairs)
    g = r["groups"]["Contract|cut=-|einsum"]
    assert g["n"] == 2
    assert g["rank_corr"] == pytest.approx(1.0)
    assert g["ratio_spread"] == pytest.approx(2.0)      # 20 / 10
    assert r["groups"]["CutJoin|cut=2|kernel"]["ratio_spread"] is None
    assert r["overall_rank_corr"] is not None


# -- per-phase batcher fallbacks ---------------------------------------------------

def test_batcher_fallback_compile_phase(monkeypatch):
    from repro import compiler as compiler_mod
    from repro.serve.batching import PatternQueryBatcher, PatternRequest

    def boom(*a, **k):
        raise RuntimeError("compiler down")

    monkeypatch.setattr(compiler_mod, "compile", boom)
    b = PatternQueryBatcher(G, max_batch=2)
    for i in range(2):
        b.submit(PatternRequest(uid=i, patterns=(chain(4),)))
    b.run_to_completion()
    assert len(b.finished) == 2
    assert b.stats["fallbacks"] == 2
    assert b.stats["fallbacks_compile"] == 2
    assert b.stats["fallbacks_execute"] == 0
    assert b.stats["errors"] == 0


def test_batcher_fallback_execute_phase(monkeypatch):
    """A plan that compiles but refuses at run time (e.g. PlanTooWide)
    must land in the execute-phase bucket, not the compile one."""
    from repro.compiler.lowering import CompiledPlan
    from repro.serve.batching import PatternQueryBatcher, PatternRequest

    def boom(self, p):
        raise RuntimeError("PlanTooWide at execution")

    monkeypatch.setattr(CompiledPlan, "count", boom)
    b = PatternQueryBatcher(G, max_batch=2)
    b.submit(PatternRequest(uid=0, patterns=(chain(4),)))
    b.run_to_completion()
    req = b.finished[0]
    assert req.done and not req.error
    assert req.counts[chain(4)] == CountingEngine(G).edge_induced(chain(4))
    assert b.stats["fallbacks"] == 1
    assert b.stats["fallbacks_execute"] == 1
    assert b.stats["fallbacks_compile"] == 0


def test_batcher_stats_dict_compat():
    """The stats facade still behaves like the old plain dict."""
    from repro.serve.batching import PatternQueryBatcher, PatternRequest
    b = PatternQueryBatcher(G, max_batch=2)
    b.submit(PatternRequest(uid=0, patterns=(clique(3),)))
    b.run_to_completion()
    assert b.stats["steps"] == 1 and b.stats["compiles"] == 1
    assert set(b.stats) >= {"steps", "compiles", "cache_hits",
                            "fallbacks", "errors"}
    assert isinstance(dict(b.stats)["steps"], int)


# -- plan cache eviction metrics ---------------------------------------------------

@pytest.mark.slow
def test_plancache_eviction_metrics(tmp_path):
    from repro.compiler import PlanCache, plan_key
    reg = obs.REGISTRY
    base_age = reg.get("plancache.eviction.age_s", default=None)
    n_before = base_age["count"] if isinstance(base_age, dict) else 0

    cache = PlanCache(str(tmp_path), max_disk_entries=2)
    pats = [chain(3), chain(4), cycle(4), cycle(5)]
    for p in pats:
        compiler.compile(p, G, counter=CountingEngine(G), cache=cache)
    assert cache.evictions >= 2
    age = reg.get("plancache.eviction.age_s", default=None)
    size = reg.get("plancache.eviction.bytes", default=None)
    assert age["count"] - n_before >= 2
    assert age["min"] >= 0.0
    assert size["min"] > 0                  # real plan files have bytes
    # instance counters stay exact and int-typed through the facade
    assert isinstance(cache.evictions, int)
    assert cache.stats["evictions"] == cache.evictions


def test_plancache_clear_keeps_registry_monotonic(tmp_path):
    from repro.compiler import PlanCache
    reg = obs.REGISTRY
    cache = PlanCache()
    compiler.compile(chain(3), G, counter=CountingEngine(G), cache=cache)
    assert cache.misses == 1
    before = reg.get("plancache.misses", tier="mem")
    cache.clear()
    assert cache.misses == 0                # local reset
    assert reg.get("plancache.misses", tier="mem") == before   # monotonic


# -- kernel / api counters ---------------------------------------------------------

def test_kernel_call_counters():
    from repro.kernels import ops
    reg = obs.REGISTRY
    labels = dict(op="cutjoin_reduce", cut=2, mode="interpret")
    before = reg.get("kernel.calls", **labels)
    M = np.ones((8, 8))
    ops.cutjoin_reduce([M, M], interpret=True)
    assert reg.get("kernel.calls", **labels) == before + 1
    granted = reg.get("kernel.exact_block", outcome="granted")
    precertified = reg.get("kernel.exact_block", outcome="precertified")
    assert granted + precertified >= 1


def test_api_compile_fallback_counter(monkeypatch):
    from repro import api
    from repro.api import local as api_local
    reg = obs.REGISTRY
    before = reg.get("api.compile_fallbacks", entry="local_counts")

    def boom(*a, **k):
        raise RuntimeError("compiler down")

    monkeypatch.setattr(api_local, "_compile_local", boom)
    lc = api.local_counts(chain(4), G)
    assert lc.counts is not None
    assert reg.get("api.compile_fallbacks",
                   entry="local_counts") == before + 1
