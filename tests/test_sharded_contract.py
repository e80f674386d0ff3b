"""Adjacency-sharded contractions (``repro.distributed.contract``).

The tentpole invariant: with the adjacency row-sharded over the
``("data",)`` mesh, every hom count and free-hom cut tensor is
bit-for-bit equal to the single-device engine — the collective route
changes where the einsums run and where the tensors live, never a
single bit of what they compute — and the dense n x n adjacency never
materialises anywhere (asserted via the engine's lazy ``_A_dense``
staying unbuilt and the ``einsum-sharded`` route annotations).

Multi-device checks spawn subprocesses with forced host devices, same
as ``test_mesh_join``; cache/cost checks are pure host code.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=560)


_DIFFERENTIAL = """
    import numpy as np
    from repro.core.counting import CountingEngine
    from repro.core.pattern import Pattern, chain, clique, cycle
    from repro.distributed import meshes
    from repro.graph import generators as gen

    mesh = meshes.data_mesh()
    d = meshes.num_shards(mesh)

    for n in (96, 97):                    # 97: not divisible by any d > 1
        for num_labels in (0, 3):
            g = gen.erdos_renyi(n, 6.0, seed=3, num_labels=num_labels)
            ref = CountingEngine(g)
            sh = CountingEngine(g, mesh=mesh)
            pats = [cycle(4), chain(4), clique(3), chain(3)]
            if num_labels:
                pats += [Pattern(4, cycle(4).edges, labels=(0, 1, 2, 0)),
                         Pattern(3, ((0, 1), (1, 2)), labels=(2, 0, 1))]
            for p in pats:
                for free in ((), (0,), (0, 1)):
                    free = tuple(v for v in free if v < p.n)
                    if free:
                        a = np.asarray(ref.hom_free_tensor(p, free))
                        b = np.asarray(sh.hom_free_tensor(p, free))
                        assert np.array_equal(a, b), \\
                            (n, num_labels, sorted(p.edges), free)
                    else:
                        assert ref.hom(p) == sh.hom(p), \\
                            (n, num_labels, sorted(p.edges))
            if d > 1:
                # the sharded engine never built a dense n x n adjacency
                assert sh._A_dense is None
                t = sh.hom_free_tensor(cycle(4), (0, 1))
                if n % d == 0:
                    # no padding -> the cut tensor stays sliced on axis 0
                    assert t.sharding.spec[0] == "data", t.sharding.spec
    print("OK")
"""


@pytest.mark.slow
def test_sharded_contract_matches_single_device_8dev():
    """The acceptance matrix at 8 devices: labelled/unlabelled,
    divisible and indivisible n, scalar homs and free tensors."""
    r = _run(_DIFFERENTIAL, devices=8)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_sharded_contract_matches_single_device_1dev():
    """Same matrix at 1 device: a 1-device mesh binds to nothing (the
    engine keeps the single-device route) and everything still agrees."""
    r = _run(_DIFFERENTIAL, devices=1)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_compiled_plan_contract_route_sharded():
    """compile(mesh=): Contract nodes take the ``einsum-sharded`` route,
    counts match the meshless plan bit-for-bit, and the mesh-bound
    engine never materialises the dense adjacency."""
    r = _run("""
        from repro import compiler, obs
        from repro.core.counting import CountingEngine
        from repro.core.motifs import motif_patterns
        from repro.distributed import meshes
        from repro.graph import generators as gen

        mesh = meshes.data_mesh()
        g = gen.erdos_renyi(96, 7.0, seed=2)
        pats = motif_patterns(4)
        eng = CountingEngine(g, mesh=mesh)
        tr = obs.Tracer()
        cp = compiler.compile(pats, g, counter=eng, cache=False, mesh=mesh)
        cp.tracer = tr
        base = compiler.compile(pats, g, counter=CountingEngine(g),
                                cache=False)
        for p in pats:
            assert cp.count(p) == base.count(p), sorted(p.edges)

        routes = {}
        def walk(s):
            r = s.attrs.get("route")
            if r:
                routes[r] = routes.get(r, 0) + 1
            for c in s.children:
                walk(c)
        for root in tr.roots:
            walk(root)
        assert "einsum-sharded" in routes, routes
        assert "einsum" not in routes, routes   # nothing fell back
        assert eng._A_dense is None
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_sharded_dense_keep_join_matches_oracle():
    """``sharded_dense_join_keep`` (the guard-refusal keep-axis route)
    against a plain-numpy oracle over pairwise-distinct cut tuples:
    k in {2, 3}, every keep axis, divisible and padding n."""
    r = _run("""
        import itertools
        import numpy as np
        from repro.distributed import cutjoin as dcj, meshes

        mesh = meshes.data_mesh()
        rng = np.random.default_rng(5)
        for n in (40, 37):                       # 37: padding path
            for k in (2, 3):
                Ms = [rng.integers(0, 5, size=(n,) * k).astype(np.float64)
                      for _ in range(2)]
                mask = np.ones((n,) * k)
                for a, b in itertools.combinations(range(k), 2):
                    shape = [1] * k
                    shape[a] = shape[b] = n
                    mask = mask * (1.0 - np.eye(n)).reshape(shape)
                stack = np.stack(Ms + [mask])
                for keep in range(k):
                    red = tuple(a + 1 for a in range(k) if a != keep)
                    ref = np.sum(np.prod(stack, axis=0), axis=tuple(
                        a for a in range(k) if a != keep))
                    got = dcj.sharded_dense_join_keep(Ms, k, keep=keep,
                                                      mesh=mesh)
                    assert np.array_equal(got, ref), (n, k, keep)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_keep_axis_guard_refusal_routes_sharded():
    """Keep-axis joins that can't take the kernel route under a mesh
    (here: kernel tier disabled outright) land on ``xla-sharded-keep``
    — not the old wholesale single-device fallback — and the per-vertex
    counts stay bit-for-bit."""
    r = _run("""
        import numpy as np
        from repro import compiler, obs
        from repro.api.local import plan_vertex_counts
        from repro.core.counting import CountingEngine
        from repro.core.pattern import chain
        from repro.distributed import meshes
        from repro.graph import generators as gen

        mesh = meshes.data_mesh()
        g = gen.erdos_renyi(96, 8.0, seed=2)
        p = chain(4)
        tr = obs.Tracer()
        cp = compiler.compile(p, g, counter=CountingEngine(g, mesh=mesh),
                              cache=False, mesh=mesh, local=True,
                              cutjoin_kernel=False)
        cp.tracer = tr
        ref = compiler.compile(p, g, counter=CountingEngine(g),
                               cache=False, local=True,
                               cutjoin_kernel=False)
        assert np.array_equal(plan_vertex_counts(cp, p),
                              plan_vertex_counts(ref, p))
        routes = set()
        def walk(s):
            routes.add(s.attrs.get("route"))
            for c in s.children:
                walk(c)
        for root in tr.roots:
            walk(root)
        assert "xla-sharded-keep" in routes, routes
        assert "xla-keep" not in routes, routes
        # the new route is not a fallback — no shard_fallbacks counted
        snap = obs.snapshot()
        assert not any("shard_fallbacks" in k for k in snap), snap
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_shard_fallback_counters_split_by_phase():
    """One fallback per phase: a fresh compile that serves a count
    increments ``..._compile`` only; re-serving the cached plan
    increments ``..._execute`` only — no double counting."""
    r = _run("""
        from repro import compiler, obs
        from repro.compiler import PlanCache
        from repro.core.counting import CountingEngine
        from repro.core.pattern import cycle
        from repro.distributed import meshes
        from repro.graph import generators as gen

        mesh = meshes.data_mesh()
        g = gen.erdos_renyi(6, 2.0, seed=1)       # n=6 < 8 -> small-n
        p = cycle(4)
        cache = PlanCache()
        c1 = compiler.compile(p, g, cache=cache, mesh=mesh).count(p)
        snap = obs.snapshot()
        compile_hits = snap.get("cutjoin.shard_fallbacks_compile", {})
        assert sum(compile_hits.values()) == 1, snap
        assert "cutjoin.shard_fallbacks_execute" not in snap, snap

        cp2 = compiler.compile(p, g, cache=cache, mesh=mesh)
        assert cp2.from_cache
        assert cp2.count(p) == c1
        snap = obs.snapshot()
        assert sum(snap["cutjoin.shard_fallbacks_compile"].values()) == 1
        assert sum(snap["cutjoin.shard_fallbacks_execute"].values()) == 1
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_plan_cache_mesh_device_compat():
    """A plan compiled with a mesh must not be served to a meshless
    caller, nor a meshless plan to a mesh-bound caller; same-mesh hits
    still serve."""
    r = _run("""
        from repro import compiler
        from repro.compiler import PlanCache
        from repro.core.pattern import cycle
        from repro.distributed import meshes
        from repro.graph import generators as gen

        mesh = meshes.data_mesh()
        g = gen.erdos_renyi(64, 6.0, seed=1)
        p = cycle(4)
        cache = PlanCache()
        a = compiler.compile(p, g, cache=cache, mesh=mesh)
        assert not a.from_cache
        assert a.plan.meta["mesh_devices"] == 8

        b = compiler.compile(p, g, cache=cache)          # meshless
        assert not b.from_cache                          # recompiled
        assert b.plan.meta["mesh_devices"] == 1

        c = compiler.compile(p, g, cache=cache, mesh=mesh)
        assert not c.from_cache                          # overwrite was meshless

        d2 = compiler.compile(p, g, cache=cache, mesh=mesh)
        assert d2.from_cache                             # same config serves
        assert a.count(p) == b.count(p) == d2.count(p)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_config_compatible_unit():
    """The compat predicate itself, including legacy entries that
    predate the ``mesh_devices`` field (valid for meshless callers
    only)."""
    from repro.compiler import config_compatible
    from repro.compiler.ir import Plan

    plan = Plan()
    plan.meta.update({"budget": 1 << 27, "max_cutjoin_cut": 3,
                      "mesh_devices": 8})
    ok = dict(budget=1 << 27, max_cutjoin_cut=3)
    assert config_compatible(plan, **ok, mesh_devices=8)
    assert not config_compatible(plan, **ok, mesh_devices=1)
    assert not config_compatible(plan, **ok, mesh_devices=4)
    assert not config_compatible(plan, budget=1, max_cutjoin_cut=3,
                                 mesh_devices=8)

    legacy = Plan()                       # written before the field existed
    legacy.meta.update({"budget": 1 << 27, "max_cutjoin_cut": 3})
    assert config_compatible(legacy, **ok, mesh_devices=1)
    assert not config_compatible(legacy, **ok, mesh_devices=8)


def test_contract_cost_devices_term():
    """More devices: per-device contraction work shrinks, a log2(d)
    per-step collective surcharge appears — never free, and a 1-device
    mesh prices identically to no mesh."""
    import math

    from repro.compiler.costing import _contract_cost
    from repro.compiler.ir import Contract
    from repro.core import homomorphism as H
    from repro.core.apct import APCT
    from repro.core.pattern import cycle
    from repro.graph import generators as gen

    g = gen.erdos_renyi(512, 6.0, seed=1)
    apct = APCT(g)
    p = cycle(4)
    node = Contract(key="c", pattern=p, order=H.greedy_plan(p, ()))
    budget = 1 << 27
    c1 = _contract_cost(node, apct, g.n, budget)
    assert c1 == _contract_cost(node, apct, g.n, budget, devices=1)
    c8 = _contract_cost(node, apct, g.n, budget, devices=8)
    assert c8 < c1                       # sharding pays off at n=512
    # the collective term is never waived: with tiny per-device work the
    # log2(d) surcharge dominates
    tiny = gen.erdos_renyi(8, 2.0, seed=2)
    t8 = _contract_cost(node, APCT(tiny), tiny.n, budget, devices=8)
    assert t8 > math.log2(8)


def test_shard_check_covers_contract_nodes():
    """``shard-budget-overflow`` now reports Contract nodes whose
    per-shard residency (row block + widest replicated intermediate)
    exceeds the cap."""
    from repro import analysis, compiler
    from repro.analysis import GraphInfo
    from repro.core.counting import CountingEngine
    from repro.core.pattern import cycle
    from repro.graph import generators as gen

    g = gen.erdos_renyi(24, 4.0, seed=13)
    cp = compiler.compile(cycle(4), g, counter=CountingEngine(g),
                          cache=False)
    info = GraphInfo.from_graph(g)
    res = analysis.shard_check(cp.plan, info, 4, budget=1)
    contract_keys = {k for k, n in cp.plan.nodes.items()
                     if type(n).__name__ == "Contract"}
    assert contract_keys, "plan has no Contract nodes?"
    flagged = {d.node for d in res.warnings
               if d.code == "shard-budget-overflow"}
    assert contract_keys & flagged, (contract_keys, flagged)
    # a sane budget flags nothing on this tiny plan
    res2 = analysis.shard_check(cp.plan, info, 4, budget=1 << 27)
    assert not [d for d in res2.warnings
                if d.code == "shard-budget-overflow"]


# -- the step forms at SCALE 6-9, on four devices -----------------------------

_GENERATORS = """
    import importlib.util
    import os

    GRAPHS = os.path.join({root!r}, "benchmarks", "gpm", "graphs")

    def generate(kind, params, seed):
        spec = importlib.util.spec_from_file_location(
            "gen_" + kind, os.path.join(GRAPHS, kind + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.generate(params, seed)

    KRON = dict(edgefactor=16, A=0.57, B=0.19, C=0.19)
"""
ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run4(code: str):
    prelude = textwrap.dedent(_GENERATORS.format(root=os.path.abspath(ROOT)))
    return _run(prelude + textwrap.dedent(code), devices=4)


_STEP_FORMS = """
    import jax
    import numpy as np
    from repro.core import homomorphism as H
    from repro.core.counting import CountingEngine
    from repro.core.motifs import motif_patterns
    from repro.core.pattern import Pattern, clique
    from repro.distributed import contract as C, meshes
    from repro.graph.storage import Graph

    mesh = meshes.data_mesh()
    assert meshes.num_shards(mesh) == 4
    pats = [p for p in motif_patterns(4) if p.m < 6] + [clique(3)]
    pats += [Pattern(4, ((0, 1), (1, 2), (2, 3), (0, 3)), labels=(0, 1, 2, 0)),
             Pattern(3, ((0, 1), (1, 2)), labels=(2, 0, 1))]
    seen = set()
    for kind, params, seed in (("kronecker", dict(KRON, SCALE=6), 3),
                               ("urand", dict(SCALE=7, degree=4), 4),
                               ("kronecker", dict(KRON, SCALE=9), 5)):
        n, edges = generate(kind, params, seed)
        labels = np.random.default_rng(seed).integers(0, 3, n)
        g = Graph(n, edges, labels)
        sh, ref = CountingEngine(g, mesh=mesh), CountingEngine(g)
        degree = int(g.degrees.max())
        with jax.enable_x64():
            blocks, A = sh._blocks(), ref.A
            for p in pats:
                for free in ((), (0,), (0, 1)):
                    for order in (H.greedy_plan(p, free), tuple(range(p.n))):
                        steps = []
                        got = C.sharded_hom(
                            p, blocks, mesh=mesh, n=n, order=order,
                            free=free, unary=sh._unary_blocks(p),
                            max_degree=degree, steps=steps)
                        want = H.hom_count(p, A, order=order, free=free,
                                           unary=ref._unary_for(p))
                        assert np.array_equal(np.asarray(got),
                                              np.asarray(want)), \\
                            (kind, sorted(p.edges), p.labels, free, order)
                        for s in steps:
                            seen.add(s["form"])
                            # a pair step over pair and vertex factors
                            # always takes the narrow form here: f64-psum
                            # only where a factor or the output is wider
                            lhs, rhs = s["spec"].split("->")
                            if s["form"] == "f64-psum":
                                assert max(map(len, lhs.split(",") + [rhs])) \\
                                    >= 3, s
    assert seen == {"int8-scatter", "vector-psum", "out-sharded-f64",
                    "f64-psum"}, seen
    print("OK")
"""


def test_sharded_step_forms_bit_equal_hom_count():
    """Every step form, on Kronecker and Urand graphs of SCALE 6-9 and
    on labelled patterns, for the 4-motif plan's patterns under both
    the greedy and the identity elimination order: bit-for-bit the f64
    ``hom_count`` over the dense adjacency."""
    r = _run4(_STEP_FORMS)
    assert "OK" in r.stdout, r.stdout + r.stderr


_REFUSED = """
    import jax
    import numpy as np
    from repro import obs
    from repro.core.counting import CountingEngine
    from repro.core.pattern import cycle
    from repro.distributed import contract as C, meshes
    from repro.graph.storage import Graph

    mesh = meshes.data_mesh()
    c5, order = cycle(5), (0, 1, 2, 3, 4)
    # eliminating 0 then 1 leaves A^3 (2 <- 1 <- 0 <- 4), whose entries
    # reach the largest degree squared: 128**2 or more with a hub of
    # degree 128 or more (Kronecker SCALE 9), below it on the Urand graph
    for kind, params, seed, refused in (
            ("kronecker", dict(KRON, SCALE=9), 5, True),
            ("urand", dict(SCALE=7, degree=4), 4, False)):
        n, edges = generate(kind, params, seed)
        g = Graph(n, edges)
        assert (int(g.degrees.max()) >= 128) == refused
        obs.reset()
        sh = CountingEngine(g, mesh=mesh)
        got = sh.hom(c5, order=order)
        assert got == CountingEngine(g).hom(c5, order=order)
        steps = obs.snapshot()["contract.steps"]
        assert steps.get("form=f64-psum", 0) == (1 if refused else 0), steps
        assert steps["form=int8-scatter"] == (2 if refused else 3), steps
        # the narrow steps' bound follows the degree it is given: at the
        # loosest one, n, the A^3 step is refused on both graphs
        with jax.enable_x64():
            rows = []
            val = C.sharded_hom(c5, sh._blocks(), mesh=mesh, n=n,
                                max_degree=n, order=order, steps=rows)
        assert float(val) == got
        assert [s["form"] for s in rows].count("f64-psum") == 1
    print("OK")
"""


def test_refused_bound_takes_the_f64_psum_form():
    """A step whose factor bound needs more than two int8 digit planes
    (A^3 on a hub-heavy graph) takes the f64 ``psum`` form, is counted,
    and stays exact; the same plan on a graph without hubs runs every
    pair step narrow."""
    r = _run4(_REFUSED)
    assert "OK" in r.stdout, r.stdout + r.stderr


_BRUTE = """
    from repro import compiler
    from repro.core.counting import CountingEngine, brute_force_edge_induced
    from repro.core.motifs import motif_patterns
    from repro.core.pattern import Pattern
    from repro.distributed import meshes
    from repro.graph.storage import Graph
    import numpy as np

    mesh = meshes.data_mesh()
    labelled = [Pattern(4, ((0, 1), (1, 2), (2, 3), (0, 3)),
                        labels=(0, 1, 0, 1)),
                Pattern(4, ((0, 1), (1, 2), (2, 3)), labels=(2, 0, 1, 0))]
    for kind, params, seed in (("kronecker", dict(KRON, SCALE=6), 6),
                               ("urand", dict(SCALE=6, degree=4), 7)):
        n, edges = generate(kind, params, seed)
        g = Graph(n, edges, np.random.default_rng(seed).integers(0, 3, n))
        eng = CountingEngine(g, mesh=mesh)
        for pats in (motif_patterns(4), labelled):
            cp = compiler.compile(pats, g, counter=eng, cache=False,
                                  mesh=mesh)
            for p in pats:
                assert cp.count(p) == brute_force_edge_induced(g, p), \\
                    (kind, sorted(p.edges), p.labels)
        assert eng._A_dense is None
    print("OK")
"""


def test_mesh_census_equals_brute_force():
    """The 4-motif census and labelled patterns through
    ``compile(mesh=)`` on four devices: integer-equal to brute force."""
    r = _run4(_BRUTE)
    assert "OK" in r.stdout, r.stdout + r.stderr


_GUARD_REFUSED = """
    import numpy as np
    from repro import compiler, obs
    from repro.api.local import plan_vertex_counts
    from repro.compiler import lowering
    from repro.core.counting import CountingEngine
    from repro.core.motifs import motif_patterns
    from repro.core.pattern import chain
    from repro.distributed import meshes
    from repro.graph.storage import Graph
    from repro.kernels import ops

    # every join's guard refuses, as Graph500's hubs make it refuse
    ops.cutjoin_exact_block = lambda *a, **k: None
    lowering.CompiledPlan._precertified = lambda self: {}
    mesh = meshes.data_mesh()
    n, edges = generate("kronecker", dict(KRON, SCALE=7), 8)
    g = Graph(n, edges)
    pats = motif_patterns(4)
    tr = obs.Tracer()
    cp = compiler.compile(pats, g, counter=CountingEngine(g, mesh=mesh),
                          cache=False, mesh=mesh)
    cp.tracer = tr
    oracle = compiler.compile(pats, g, counter=CountingEngine(g),
                              cache=False, cutjoin_kernel=False)
    for p in pats:
        assert cp.count(p) == oracle.count(p), sorted(p.edges)
    joins = {s.attrs["route"] for s in tr.walk() if s.kind == "CutJoin"}
    assert joins == {"xla-sharded"}, joins
    assert not cp._masks                 # no host mask, built in the shards

    p = chain(4)
    tr = obs.Tracer()
    cp = compiler.compile(p, g, counter=CountingEngine(g, mesh=mesh),
                          cache=False, mesh=mesh, local=True)
    cp.tracer = tr
    oracle = compiler.compile(p, g, counter=CountingEngine(g), cache=False,
                              local=True, cutjoin_kernel=False)
    assert np.array_equal(plan_vertex_counts(cp, p),
                          plan_vertex_counts(oracle, p))
    routes = {s.attrs.get("route") for s in tr.walk()}
    assert "xla-sharded-keep" in routes, routes
    assert not cp._masks
    print("OK")
"""


def test_guard_refused_mesh_join_matches_xla_dense_oracle():
    """Joins whose ``exact_block`` guard refuses run on the mesh as the
    f64 ``xla-sharded`` / ``xla-sharded-keep`` joins, their injectivity
    mask built from iotas inside each shard (no host mask): equal to the
    single-device ``xla-dense`` oracle."""
    r = _run4(_GUARD_REFUSED)
    assert "OK" in r.stdout, r.stdout + r.stderr
