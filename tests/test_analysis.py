"""Static-analysis layer: mutation-tests the plan-IR verifier (every
seeded corruption rejected with its own diagnostic, golden plans verify
with zero false positives), cache corruption recovery through the
verifier, the exact_block precertification path (no runtime guard scan,
bit-for-bit with the XLA oracle), and the AST lint rules."""
import dataclasses
import json
import math

import numpy as np
import pytest

from repro import analysis, compiler, obs
from repro.analysis import lint as lint_mod
from repro.analysis.verify import GraphInfo, PlanVerifyError, _hom_free_bound
from repro.compiler import frontend, lowering
from repro.compiler.cache import PlanCache
from repro.compiler.ir import (Contract, CutJoin, Intersect, LocalCount,
                               MobiusCombine, Plan, PlanFormatError,
                               ShrinkageCorrect, local_key, pattern_key)
from repro.core import homomorphism as H
from repro.core.counting import CountingEngine
from repro.core.decomposition import cutting_sets
from repro.core.pattern import (Pattern, chain, clique, cycle, mark_free,
                                tailed_triangle)
from repro.graph.generators import erdos_renyi
from repro.graph.storage import Graph

K5_MINUS_EDGE = Pattern(5, [(u, v) for u in range(5)
                            for v in range(u + 1, 5) if (u, v) != (3, 4)])

G = erdos_renyi(24, 4.0, seed=1)


def _compile(pats, g=G, **kw):
    return compiler.compile(pats, g, counter=CountingEngine(g),
                            cache=False, **kw)


# -- plan factories (fresh per corruption — corruptions mutate) --------------------

def _decomposed_plan(p=None):
    """Legacy full-cut |cut| = 2 plan for cycle(4)."""
    p = p or cycle(4)
    cand = frontend.decomposed_candidate(p, frozenset({0, 2}), graph_n=G.n,
                                         budget=1 << 27, max_cut=3)
    assert cand is not None
    return frontend.assemble([(p, cand)])


def _subset_plan():
    """Axis-subset |cut| = 3 plan for K5-minus-edge."""
    p = K5_MINUS_EDGE
    cut = min((c for c in cutting_sets(p) if len(c) == 3), key=sorted)
    cand = frontend.decomposed_candidate(p, cut, graph_n=G.n,
                                         budget=1 << 27, max_cut=3)
    assert cand is not None and cand.style == "decomposed-subset"
    return frontend.assemble([(p, cand)])


def _local_plan():
    """Anchored keep-axis LocalCount plan for cycle(4)."""
    p = cycle(4)
    cand = frontend.local_candidate(p, frozenset({0, 2}), graph_n=G.n,
                                    anchor=0, budget=1 << 27, max_cut=3)
    assert cand is not None
    plan = Plan()
    for node in cand.nodes:
        plan.add(node)
    plan.set_local_output(p, cand.out_key, anchor=0)
    return plan


def _direct_clique_plan():
    cand = frontend.direct_candidate(clique(4))
    return frontend.assemble([(clique(4), cand)])


def _free_contract(p, free, key):
    """A well-formed marker-encoded free-hom Contract over ``p``."""
    _, qc, free_c = mark_free(p, free)
    return Contract(key, qc, H.greedy_plan(qc, free_c), free_c)


def _node_of(plan, cls):
    return next(k for k, n in plan.nodes.items() if isinstance(n, cls))


def _replace(plan, key, **repl):
    plan.nodes[key] = dataclasses.replace(plan.nodes[key], **repl)
    return plan


# -- the mutation corpus -----------------------------------------------------------
#
# Each entry seeds ONE corruption class and names the diagnostic code
# that must reject it.  Expected codes are pairwise distinct across the
# corpus — the verifier distinguishes every failure class, not just
# "invalid".  Entries return (plan, verify_kwargs).

def _c_dangling_ref():
    plan = _decomposed_plan()
    key = _node_of(plan, ShrinkageCorrect)
    return _replace(plan, key, corrections=((1.0, "ghost:node"),)), {}


def _c_cycle():
    plan = _decomposed_plan()
    plan.nodes["a:x"] = MobiusCombine("a:x", ((1.0, "b:x"),))
    plan.nodes["b:x"] = MobiusCombine("b:x", ((1.0, "a:x"),))
    return plan, {}


def _c_key_mismatch():
    plan = _decomposed_plan()
    key = _node_of(plan, Contract)
    plan.nodes["not:" + key] = plan.nodes[key]
    return plan, {}


def _c_output_missing():
    plan = _decomposed_plan()
    plan.outputs["9.99"] = "ghost:node"
    return plan, {}


def _c_unknown_node_class():
    plan = _decomposed_plan()
    plan.nodes["alien"] = object()
    return plan, {}


def _c_axis_out_of_range():
    plan = _subset_plan()
    key = _node_of(plan, CutJoin)
    join = plan.nodes[key]
    i = next(i for i, a in enumerate(join.axes) if len(a) == 2)
    axes = tuple((0, 7) if j == i else a for j, a in enumerate(join.axes))
    return _replace(plan, key, axes=axes), {}


def _c_axes_arity():
    plan = _subset_plan()
    key = _node_of(plan, CutJoin)
    join = plan.nodes[key]
    return _replace(plan, key, axes=join.axes[:-1]), {}


def _c_cut_uncovered():
    plan = _decomposed_plan()
    ref = _node_of(plan, Contract)          # rank-2 free-hom tensor
    plan.nodes["cj:test"] = CutJoin(
        "cj:test", 3, (((1.0, ref),), ((1.0, ref),)),
        axes=((0, 1), (0, 1)))              # rank 2 never spanned
    return plan, {}


def _c_illegal_subset_axes():
    plan = _decomposed_plan()
    vec = _free_contract(chain(2), (0,), "homf:vec-test")
    plan.nodes[vec.key] = vec
    plan.nodes["cj:test"] = CutJoin(
        "cj:test", 2, (((1.0, vec.key),), ((1.0, vec.key),)),
        axes=((0,), (1,)))                  # subsets at |cut| = 2
    return plan, {}


def _c_keep_outside_cut():
    plan = _local_plan()
    return _replace(plan, _node_of(plan, LocalCount), keep=(5,)), {}


def _c_illegal_keep():
    plan = _subset_plan()
    ref3 = next(k for k, n in plan.nodes.items()
                if isinstance(n, Contract) and len(n.free) == 3)
    plan.nodes["lc:test"] = LocalCount("lc:test", 3, (0, 1),
                                       (((1.0, ref3),),))
    return plan, {}


def _c_illegal_route():
    plan = _decomposed_plan()
    r4 = _free_contract(chain(5), (0, 1, 2, 3), "homf:r4-test")
    plan.nodes[r4.key] = r4
    plan.nodes["lc:test"] = LocalCount("lc:test", 4, (0,),
                                       (((1.0, r4.key),),))
    return plan, {}


def _c_budget_overflow():
    # a committed 3-cut join whose factor elements blow 4x a tiny budget
    return _subset_plan(), {"graph_info": GraphInfo(24, 8, 2), "budget": 10}


def _c_bad_label_encoding():
    plan = _decomposed_plan()
    key = _node_of(plan, Contract)
    node = plan.nodes[key]
    stripped = Pattern(node.pattern.n, node.pattern.edges)   # markers gone
    return _replace(plan, key, pattern=stripped), {}


def _c_bad_divisor():
    plan = _decomposed_plan()
    return _replace(plan, _node_of(plan, ShrinkageCorrect), divisor=0), {}


def _c_bad_intersect():
    plan = _direct_clique_plan()
    return _replace(plan, _node_of(plan, Intersect), k=2), {}


def _c_shape_mismatch():
    plan = _decomposed_plan()
    key = _node_of(plan, CutJoin)
    join = plan.nodes[key]
    scalar = Contract("hom:scalar-test", cycle(4),
                      H.greedy_plan(cycle(4)))
    plan.nodes[scalar.key] = scalar
    factors = (((1.0, scalar.key),),) + join.factors[1:]
    return _replace(plan, key, factors=factors), {}


def _c_bad_shrinkage_base():
    plan = _decomposed_plan()
    tensor = _node_of(plan, Contract)       # rank-2, not a scalar join
    return _replace(plan, _node_of(plan, ShrinkageCorrect), base=tensor), {}


def _c_bad_coefficient():
    plan = _decomposed_plan()
    key = _node_of(plan, CutJoin)
    join = plan.nodes[key]
    (c0, r0), *rest = join.factors[0]
    factors = ((((float("nan"), r0),) + tuple(rest)),) + join.factors[1:]
    return _replace(plan, key, factors=factors), {}


def _c_empty_join():
    plan = _decomposed_plan()
    return _replace(plan, _node_of(plan, CutJoin), factors=()), {}


def _c_bad_cut_size():
    plan = _decomposed_plan()
    return _replace(plan, _node_of(plan, CutJoin), cut_size=0), {}


def _c_output_shape():
    plan = _decomposed_plan()
    plan.outputs[pattern_key(cycle(4))] = _node_of(plan, Contract)
    return plan, {}


def _c_bad_free():
    plan = _decomposed_plan()
    key = _node_of(plan, Contract)
    node = plan.nodes[key]
    return _replace(plan, key, free=(node.free[0],) * 2), {}


CORPUS = [
    ("dangling-ref", _c_dangling_ref),
    ("cycle", _c_cycle),
    ("key-mismatch", _c_key_mismatch),
    ("output-missing", _c_output_missing),
    ("unknown-node-class", _c_unknown_node_class),
    ("axis-out-of-range", _c_axis_out_of_range),
    ("axes-arity", _c_axes_arity),
    ("cut-uncovered", _c_cut_uncovered),
    ("illegal-subset-axes", _c_illegal_subset_axes),
    ("keep-outside-cut", _c_keep_outside_cut),
    ("illegal-keep", _c_illegal_keep),
    ("illegal-route", _c_illegal_route),
    ("budget-overflow", _c_budget_overflow),
    ("bad-label-encoding", _c_bad_label_encoding),
    ("bad-divisor", _c_bad_divisor),
    ("bad-intersect", _c_bad_intersect),
    ("shape-mismatch", _c_shape_mismatch),
    ("bad-shrinkage-base", _c_bad_shrinkage_base),
    ("bad-coefficient", _c_bad_coefficient),
    ("empty-join", _c_empty_join),
    ("bad-cut-size", _c_bad_cut_size),
    ("output-shape", _c_output_shape),
    ("bad-free", _c_bad_free),
]


def test_corpus_codes_pairwise_distinct():
    codes = [code for code, _ in CORPUS]
    assert len(set(codes)) == len(codes)
    assert len(codes) >= 10                  # the issue's floor, 2x over


@pytest.mark.parametrize("expected,build",
                         CORPUS, ids=[c for c, _ in CORPUS])
def test_mutation_rejected_with_its_diagnostic(expected, build):
    plan, kw = build()
    res = analysis.verify(plan, **kw)
    assert not res.ok, expected
    assert expected in {d.code for d in res.errors}, \
        (expected, [str(d) for d in res.errors])


def test_uncorrupted_factories_verify_clean():
    """The corpus factories start from valid plans — the rejection is
    the corruption's doing, not the construction's."""
    for plan in (_decomposed_plan(), _subset_plan(), _local_plan(),
                 _direct_clique_plan()):
        res = analysis.verify(plan)
        assert res.ok, str(res)


# -- golden plans: zero false positives --------------------------------------------

GOLDEN = [
    ((cycle(4),), {}),
    ((chain(5),), {}),
    ((K5_MINUS_EDGE,), {}),
    ((clique(3), clique(4)), {}),
    ((cycle(4), chain(4)), {"local": True}),
    ((K5_MINUS_EDGE,), {"local": True}),     # locd: Möbius-fallback orbit
    ((chain(4),), {"domains": True}),
]


@pytest.mark.parametrize("pats,kw", GOLDEN,
                         ids=[f"golden{i}" for i in range(len(GOLDEN))])
def test_golden_plans_verify_clean(pats, kw):
    cp = _compile(pats, **kw)
    res = analysis.verify(cp.plan)           # meta carries graph_info/budget
    assert res.ok and not res.warnings, str(res)


def test_golden_labelled_plan_verifies_clean():
    g = erdos_renyi(24, 4.0, seed=1, num_labels=3)
    p = Pattern(4, [(0, 1), (1, 2), (2, 3), (3, 0)], (0, 1, 0, 1))
    cp = _compile((p,), g=g, local=True)
    res = analysis.verify(cp.plan)
    assert res.ok, str(res)


def test_infer_shapes_matches_execution():
    cp = _compile((cycle(4), chain(4)), local=True)
    shapes = analysis.infer_shapes(cp.plan, G.n)
    for name, target in cp.plan.outputs.items():
        got = cp.value(target)
        assert np.shape(np.asarray(got)) == shapes[target][0], name


# -- PlanFormatError / cache corruption --------------------------------------------

def test_plan_format_error_is_typed_valueerror():
    d = _decomposed_plan().to_dict()
    d["version"] = 999
    with pytest.raises(PlanFormatError):
        Plan.from_dict(d)
    with pytest.raises(ValueError):          # existing handlers keep working
        Plan.from_dict(d)
    with pytest.raises(PlanFormatError):
        from repro.compiler.ir import op_from_dict
        op_from_dict({"op": "nonsense"})


def _seed_cache(tmp_path):
    cache = PlanCache(str(tmp_path))
    cache.put("k1", _decomposed_plan())
    return tmp_path / "plan-k1.json"


def test_cache_truncated_entry_misses_cleanly(tmp_path):
    f = _seed_cache(tmp_path)
    f.write_text(f.read_text()[:40])
    fresh = PlanCache(str(tmp_path))
    assert fresh.get("k1") is None
    assert fresh.misses == 1 and fresh.format_misses == 1
    assert fresh.verify_rejects == 0


def test_cache_field_dropped_entry_misses_cleanly(tmp_path):
    f = _seed_cache(tmp_path)
    d = json.loads(f.read_text())
    node = next(n for n in d["nodes"] if n["op"] == "shrinkage")
    del node["divisor"]
    f.write_text(json.dumps(d))
    fresh = PlanCache(str(tmp_path))
    assert fresh.get("k1") is None
    assert fresh.format_misses == 1 and fresh.verify_rejects == 0


def test_cache_bit_flipped_entry_rejected_by_verifier(tmp_path):
    """A single-bit flip the schema can't see: cut_size 2 -> 3 still
    parses, but the verifier catches the rank mismatch — without it this
    entry would lower and serve garbage."""
    f = _seed_cache(tmp_path)
    data = bytearray(f.read_bytes())
    i = bytes(data).index(b'"cut_size": 2') + len(b'"cut_size": ')
    data[i] ^= 0x01                           # ASCII '2' -> '3'
    f.write_bytes(bytes(data))
    assert json.loads(f.read_text())          # parses fine
    fresh = PlanCache(str(tmp_path))
    assert fresh.get("k1") is None
    assert fresh.verify_rejects == 1 and fresh.format_misses == 0
    assert fresh.misses == 1


def test_cache_verify_opt_out_loads_corrupt_entry(tmp_path):
    f = _seed_cache(tmp_path)
    data = bytearray(f.read_bytes())
    i = bytes(data).index(b'"cut_size": 2') + len(b'"cut_size": ')
    data[i] ^= 0x01
    f.write_bytes(bytes(data))
    trusting = PlanCache(str(tmp_path), verify=False)
    assert trusting.get("k1") is not None     # the gap verify=True closes


def test_cache_valid_entry_still_hits_through_verifier(tmp_path):
    _seed_cache(tmp_path)
    fresh = PlanCache(str(tmp_path))
    assert fresh.get("k1") is not None
    assert fresh.hits == 1 and fresh.verify_rejects == 0
    assert fresh.format_misses == 0


def test_compile_roundtrip_verifies_hypothesis():
    """Property: compile a random small pattern set, serialize,
    deserialize, verify — the frontend only emits plans the verifier
    accepts, through a JSON round-trip."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    pool = [cycle(4), chain(3), chain(5), tailed_triangle(), clique(3),
            cycle(5)]
    eng = CountingEngine(G)

    @settings(max_examples=6, deadline=None)
    @given(st.lists(st.integers(0, len(pool) - 1), min_size=1, max_size=2,
                    unique=True), st.booleans())
    def check(idx, local):
        pats = tuple(pool[i] for i in idx)
        cp = compiler.compile(pats, G, counter=eng, cache=False,
                              local=local)
        back = Plan.from_json(cp.plan.to_json())
        assert back == cp.plan
        res = analysis.verify(back)
        assert res.ok, str(res)
        assert res.precert == cp.plan.meta["precert"]

    check()


# -- exact_block precertification --------------------------------------------------

def test_hom_free_bound_is_sound():
    eng = CountingEngine(G)
    for p, free in [(chain(3), (0, 2)), (chain(4), (0, 3)),
                    (cycle(4), (0, 2))]:
        actual = float(np.max(np.abs(
            np.asarray(eng.hom_free_tensor(p, free)))))
        bound = _hom_free_bound(p, free, GraphInfo.from_graph(G))
        assert bound >= actual, (p, bound, actual)


def test_precertified_plan_skips_guard_scan_bit_for_bit():
    cp = _compile((cycle(4),))
    assert cp.plan.meta["precert"], "2-cut join on a sparse graph " \
        "should precertify"
    tr = obs.Tracer()
    cp.tracer = tr
    got = cp.count(cycle(4))
    kinds = [s.kind for s in tr.walk()]
    assert "guard-scan" not in kinds, kinds
    joins = [s for s in tr.walk() if s.kind == "CutJoin"]
    assert joins
    for s in joins:
        assert s.attrs["route"] == "kernel"
        assert s.attrs["precertified"] and s.attrs["exact_block"] is not None
    oracle = _compile((cycle(4),), cutjoin_kernel=False)
    assert got == oracle.count(cycle(4))      # bit-for-bit vs XLA


def test_unprecertified_plan_still_guard_scans():
    n = 40
    dense = Graph(n, np.array([(u, v) for u in range(n)
                               for v in range(u + 1, n)]))
    cp = _compile((chain(6),), g=dense)
    assert cp.plan.meta["precert"] == {}      # degree bound blows the limit
    tr = obs.Tracer()
    cp.tracer = tr
    got = cp.count(chain(6))
    assert "guard-scan" in [s.kind for s in tr.walk()]
    oracle = _compile((chain(6),), g=dense, cutjoin_kernel=False)
    assert got == oracle.count(chain(6))


def test_always_refused_flagged_at_verify_time():
    plan = _compile((cycle(4),)).plan
    huge = GraphInfo(n=4096, max_degree=4095, min_degree=4000)
    res = analysis.verify(plan, graph_info=huge)
    assert res.ok
    assert "always-refused" in {d.code for d in res.warnings}
    assert analysis.precertify(plan, huge) == {}


def test_lower_verify_flag_rejects_corrupt_plan():
    plan, _ = _c_shape_mismatch()
    with pytest.raises(PlanVerifyError):
        lowering.lower(plan, G, verify=True)
    lowering.lower(plan, G)                   # binding alone stays lazy


def test_batcher_verify_plans_param_threads_through():
    from repro.serve.batching import PatternQueryBatcher, PatternRequest
    b = PatternQueryBatcher(G, cache=PlanCache(), verify_plans=True)
    b.submit(PatternRequest(uid=1, patterns=(chain(3),)))
    b.run_to_completion()
    (done,) = b.finished
    assert done.counts and not done.error


# -- lint rules --------------------------------------------------------------------

def _findings(src):
    return lint_mod.lint_source(src, "t.py")


def test_lint_time_time_and_suppression():
    bad = "import time\nt0 = time.time()\n"
    assert [f.rule for f in _findings(bad)] == ["no-time-time"]
    ok = "import time\nt0 = time.time()  # lint: allow=no-time-time\n"
    assert _findings(ok) == []
    fine = "import time\nt0 = time.perf_counter()\n"
    assert _findings(fine) == []


def test_lint_mutable_default():
    bad = "def f(x, acc=[]):\n    return acc\n"
    assert [f.rule for f in _findings(bad)] == ["no-mutable-default"]
    bad2 = "def f(*, memo=dict()):\n    return memo\n"
    assert [f.rule for f in _findings(bad2)] == ["no-mutable-default"]
    ok = "def f(x, acc=None, k=()):\n    return acc\n"
    assert _findings(ok) == []


def test_lint_kernel_guard_protocol():
    bad = ("from repro.kernels import ops\n"
           "def join(Ms):\n"
           "    return ops.cutjoin_reduce(Ms, block=128)\n")
    assert [f.rule for f in _findings(bad)] == ["kernel-guard"]
    ok = ("from repro.kernels import ops\n"
          "def join(Ms):\n"
          "    block = ops.cutjoin_exact_block(Ms)\n"
          "    if block is None:\n"
          "        return None\n"
          "    return ops.cutjoin_reduce(Ms, block=block)\n")
    assert _findings(ok) == []
    # class scope counts: a guard helper method covers sibling methods
    ok2 = ("from repro.kernels import ops\n"
           "class P:\n"
           "    def guard(self, Ms):\n"
           "        return ops.cutjoin_exact_block(Ms)\n"
           "    def join(self, Ms):\n"
           "        b = self.guard(Ms)\n"
           "        return ops.cutjoin_reduce(Ms, block=b)\n")
    assert _findings(ok2) == []


def test_lint_ir_dict_complete():
    bad = ("from dataclasses import dataclass\n"
           "@dataclass(frozen=True)\n"
           "class Op:\n"
           "    key: str\n"
           "    extra: int\n"
           "    def refs(self):\n"
           "        return ()\n"
           "    def to_dict(self):\n"
           "        return {'key': self.key}\n"
           "def op_from_dict(d):\n"
           "    return Op(d['key'], 0)\n")
    rules = sorted(f.rule for f in _findings(bad))
    assert rules == ["ir-dict-complete", "ir-dict-complete"]  # both sides
    # plain dataclasses without the IR-op shape are out of scope
    ok = ("from dataclasses import dataclass\n"
          "@dataclass\n"
          "class Cfg:\n"
          "    key: str\n"
          "    extra: int\n")
    assert _findings(ok) == []


def test_lint_clean_over_src_repro():
    """The CI gate, as a test: the lint runs clean over the package."""
    import repro
    from pathlib import Path
    pkg = Path(next(iter(repro.__path__)))
    findings = lint_mod.lint_paths([pkg])
    assert findings == [], "\n".join(str(f) for f in findings)


def test_lint_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nt0 = time.time()\n")
    assert lint_mod.main([str(bad)]) == 1
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    assert lint_mod.main([str(good)]) == 0
    assert lint_mod.main(["--list-rules"]) == 0
