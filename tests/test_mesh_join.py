"""Mesh-execution tier: block-sharded CutJoin factors and data-parallel
request fan-out (``repro.distributed.cutjoin``).

Every sharded result must be bit-for-bit equal to its single-device
oracle — the mesh tier changes where flops run, never what they
compute.  Multi-device checks spawn subprocesses with forced host
devices (the main pytest process keeps its ambient device count, so
the suite passes identically on the single-device CI leg and the
``--xla_force_host_platform_device_count=8`` leg); cost-model and
verifier checks are pure host code.
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


def test_sharded_joins_match_single_device():
    """The kernel-level equality matrix: cut sizes 1-3, non-divisible n
    (padding path), axis-subset tri factors, keep-axis locals, and the
    sharded dense join — all bit-for-bit against the single-device
    wrappers on an 8-way forced host mesh."""
    r = _run("""
        import numpy as np
        from repro.distributed import cutjoin as dcj, meshes
        from repro.kernels import ops

        mesh = meshes.data_mesh()
        assert meshes.num_shards(mesh) == 8
        rng = np.random.default_rng(0)

        for n in (40, 65, 130):              # 65, 130: padding path
            v = [rng.integers(0, 7, size=(n,)).astype(np.float64)
                 for _ in range(2)]
            b = ops.cutjoin_exact_block(v); assert b is not None
            assert dcj.sharded_cutjoin(v, mesh=mesh, distinct=False,
                                       block=b) == \\
                ops.cutjoin_reduce(v, distinct=False, block=b), n

            Ms = [rng.integers(0, 6, size=(n, n)).astype(np.float64)
                  for _ in range(3)]
            b = ops.cutjoin_exact_block(Ms); assert b is not None
            assert dcj.sharded_cutjoin(Ms, mesh=mesh, block=b) == \\
                ops.cutjoin_reduce(Ms, block=b), n

            for keep in (0, 1):
                got = dcj.sharded_cutjoin_keep(Ms, keep=keep, mesh=mesh,
                                               block=b)
                ref = ops.cutjoin_reduce_keep(Ms, keep=keep, block=b)
                assert np.array_equal(got, ref), (n, keep)

        axes = [(0, 1), (1, 2), (0, 2)]      # axis-subset tri factors
        for n in (24, 33):                   # 33: padding path
            Ms = [rng.integers(0, 5, size=(n, n)).astype(np.float64)
                  for _ in axes]
            b = ops.cutjoin_exact_block(Ms); assert b is not None
            assert dcj.sharded_cutjoin3(Ms, axes, n=n, mesh=mesh,
                                        block=b) == \\
                ops.cutjoin_reduce3(Ms, axes, n=n, block=b), n
            for keep in (0, 1, 2):
                got = dcj.sharded_cutjoin3_keep(Ms, axes, keep=keep, n=n,
                                                mesh=mesh, block=b)
                ref = ops.cutjoin_reduce3_keep(Ms, axes, keep=keep, n=n,
                                               block=b)
                assert np.array_equal(got, ref), (n, keep)

        # full 3-D factor alongside a pair factor
        n = 26
        Ms = [rng.integers(0, 4, size=(n, n, n)).astype(np.float64),
              rng.integers(0, 4, size=(n, n)).astype(np.float64)]
        axes = [(0, 1, 2), (0, 2)]
        b = ops.cutjoin_exact_block(Ms); assert b is not None
        assert dcj.sharded_cutjoin3(Ms, axes, n=n, mesh=mesh, block=b) == \\
            ops.cutjoin_reduce3(Ms, axes, n=n, block=b)

        # dense fallback route: f64, no guard, big magnitudes welcome;
        # summed over pairwise-distinct cut tuples only
        import itertools
        import jax, jax.numpy as jnp
        big = float(1 << 30)
        for n, k in ((33, 2), (17, 3)):
            Ms = [rng.integers(0, 3, size=(n,) * k).astype(np.float64)
                  * big for _ in range(2)]
            mask = np.ones((n,) * k)
            for a, b in itertools.combinations(range(k), 2):
                shape = [1] * k
                shape[a] = shape[b] = n
                mask = mask * (1.0 - np.eye(n)).reshape(shape)
            with jax.enable_x64():
                ref = float(jnp.sum(jnp.prod(jnp.stack(
                    [jnp.asarray(M) for M in Ms + [mask]]), axis=0)))
            assert dcj.sharded_dense_join(Ms, k, mesh=mesh) == ref, (n, k)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_sharded_joins_lane_width_tiles():
    """The sharded routes at the TPU's 128-lane tile with certified
    chunks below it (8, 32): bit-for-bit against single-device."""
    r = _run("""
        import numpy as np
        from repro.distributed import cutjoin as dcj, meshes
        from repro.kernels import ops

        mesh = meshes.data_mesh()
        rng = np.random.default_rng(3)
        n, n3 = 300, 130
        Ms = [rng.integers(0, 40, size=(n, n)).astype(np.float64)
              for _ in range(2)]
        axes = [(0, 1), (1, 2), (0, 2)]
        Ts = [rng.integers(0, 6, size=(n3, n3)).astype(np.float64)
              for _ in axes]
        for b in (8, 32):
            kw = dict(block=b, tile=128)
            assert dcj.sharded_cutjoin(Ms, mesh=mesh, **kw) == \\
                ops.cutjoin_reduce(Ms, **kw), b
            assert dcj.sharded_cutjoin3(Ts, axes, n=n3, mesh=mesh, **kw) \\
                == ops.cutjoin_reduce3(Ts, axes, n=n3, **kw), b
            for keep in (0, 1):
                assert np.array_equal(
                    dcj.sharded_cutjoin_keep(Ms, keep=keep, mesh=mesh,
                                             **kw),
                    ops.cutjoin_reduce_keep(Ms, keep=keep, **kw)), (b, keep)
            for keep in (0, 1, 2):
                assert np.array_equal(
                    dcj.sharded_cutjoin3_keep(Ts, axes, keep=keep, n=n3,
                                              mesh=mesh, **kw),
                    ops.cutjoin_reduce3_keep(Ts, axes, keep=keep, n=n3,
                                             **kw)), (b, keep)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_mesh_plan_counts_bitforbit():
    """Compiled plans with a mesh bound: counts (unlabelled and
    labelled) and keep-axis local counts bit-for-bit equal to the
    meshless plan, with the sharded routes actually taken."""
    r = _run("""
        from repro import compiler, obs
        from repro.core.counting import CountingEngine
        from repro.core.pattern import Pattern, chain, cycle
        from repro.distributed import meshes
        from repro.graph import generators as gen

        mesh = meshes.data_mesh(4)
        g = gen.erdos_renyi(72, 7.0, seed=3)
        pats = (cycle(4), chain(4))
        base = compiler.compile(pats, g, counter=CountingEngine(g),
                                cache=False)
        tr = obs.Tracer()
        cp = compiler.compile(pats, g, counter=CountingEngine(g),
                              cache=False, mesh=mesh)
        cp.tracer = tr
        for p in pats:
            assert cp.count(p) == base.count(p), p

        routes = set()
        def walk(s):
            routes.add(s.attrs.get("route"))
            for c in s.children:
                walk(c)
        for root in tr.roots:
            walk(root)
        assert ("kernel-sharded" in routes or "xla-sharded" in routes), \\
            routes

        # labelled pattern through the same mesh-bound pipeline
        gl = gen.erdos_renyi(60, 6.0, seed=5, num_labels=3)
        pl = Pattern(3, [(0, 1), (1, 2)], labels=(0, 1, 0))
        bl = compiler.compile((pl,), gl, counter=CountingEngine(gl),
                              cache=False)
        cl = compiler.compile((pl,), gl, counter=CountingEngine(gl),
                              cache=False, mesh=mesh)
        assert cl.count(pl) == bl.count(pl)

        # keep-axis local counts (anchored per-vertex vectors)
        import numpy as np
        p = cycle(4)
        b2 = compiler.compile(p, g, counter=CountingEngine(g),
                              cache=False, local=True)
        c2 = compiler.compile(p, g, counter=CountingEngine(g),
                              cache=False, local=True, mesh=mesh)
        for anchor in range(p.n):
            if not b2.has_local(p, anchor):
                continue
            assert np.array_equal(c2.local_counts(p, anchor),
                                  b2.local_counts(p, anchor)), anchor
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_small_graph_falls_back_single_device():
    """n < shards: the executor refuses to shard wholesale, counts the
    ``cutjoin.shard_fallbacks_compile`` reason (phase-split — serving a
    cached plan counts ``..._execute`` instead), and still serves exact
    counts."""
    r = _run("""
        from repro import compiler, obs
        from repro.core.counting import CountingEngine
        from repro.core.pattern import cycle
        from repro.distributed import meshes
        from repro.graph import generators as gen

        mesh = meshes.data_mesh(8)
        g = gen.erdos_renyi(6, 2.0, seed=2)       # n=6 < 8 shards
        p = cycle(4)
        base = compiler.compile(p, g, counter=CountingEngine(g),
                                cache=False).count(p)
        got = compiler.compile(p, g, counter=CountingEngine(g),
                               cache=False, mesh=mesh).count(p)
        assert got == base, (got, base)
        snap = obs.snapshot()
        assert any("shard_fallbacks" in k for k in snap), snap
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_guard_refusal_under_mesh_stays_exact():
    """Factor magnitudes past ``exact_block``'s bound: the kernel route
    refuses, the mesh tier lands on the sharded (or single-device)
    dense route and the count still matches the meshless plan."""
    r = _run("""
        import numpy as np
        from repro import compiler
        from repro.core.counting import CountingEngine
        from repro.core.pattern import cycle
        from repro.distributed import meshes
        from repro.graph import generators as gen
        from repro.kernels import ops

        mesh = meshes.data_mesh(4)
        g = gen.erdos_renyi(64, 6.0, seed=7)
        p = cycle(4)
        base = compiler.compile(p, g, counter=CountingEngine(g),
                                cache=False)
        cp = compiler.compile(p, g, counter=CountingEngine(g),
                              cache=False, mesh=mesh)

        # poison the factor magnitudes the way a pathological graph
        # would: the guard must refuse, the count must not change route
        big = float(1 << 30)
        Ms = [np.full((16, 16), big), np.full((16, 16), big)]
        assert ops.cutjoin_exact_block(Ms) is None
        assert cp.count(p) == base.count(p)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_batcher_mesh_fanout_matches_single():
    """PatternQueryBatcher with a mesh: grouped requests fan out over
    device slots and every count equals the meshless batcher's."""
    r = _run("""
        from repro.core.pattern import chain, cycle
        from repro.distributed import meshes
        from repro.graph import generators as gen
        from repro.serve.batching import PatternQueryBatcher, PatternRequest

        g = gen.erdos_renyi(56, 6.0, seed=9)
        pats = (cycle(4), chain(4))
        reqs = lambda: [PatternRequest(uid=i, patterns=pats)
                        for i in range(6)]

        plain = PatternQueryBatcher(g, max_batch=8)
        for q in reqs():
            plain.submit(q)
        plain.run_to_completion()

        meshed = PatternQueryBatcher(g, max_batch=8,
                                     mesh=meshes.data_mesh())
        for q in reqs():
            meshed.submit(q)
        meshed.run_to_completion()

        assert len(plain.finished) == len(meshed.finished) == 6
        for a, b in zip(plain.finished, meshed.finished):
            assert not a.error and not b.error
            assert a.counts == b.counts, (a.counts, b.counts)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_join_batch_matches_serial():
    """MeshExecutor.join_batch on the ambient mesh (any device count):
    one fused dispatch, bit-for-bit with per-request kernel calls."""
    import numpy as np
    from repro.distributed import cutjoin as dcj, meshes
    from repro.kernels import ops

    rng = np.random.default_rng(11)
    stacks = rng.integers(0, 6, size=(11, 2, 48, 48)).astype(np.float64)
    block = min(b for b in (ops.cutjoin_exact_block(list(s))
                            for s in stacks) if b is not None)
    serial = np.asarray([ops.cutjoin_reduce(list(s), block=block)
                         for s in stacks])
    ex = dcj.MeshExecutor(meshes.data_mesh())
    assert np.array_equal(ex.join_batch(stacks), serial)


# -- cost model: tile floors and the per-device collective term ---------------------


def test_tile_floor_matches_legacy_above_tile():
    from repro.compiler.costing import DENSE_TILE, tile_floor
    for n in (128, 200, 512, 1024):
        for w in (1, 2, 3):
            legacy = (max(n, DENSE_TILE) / DENSE_TILE) ** w
            assert tile_floor(n, w) == pytest.approx(legacy), (n, w)


def test_tile_floor_differentiates_small_n():
    """The ROADMAP sharp edge: below the tile size the old floor pinned
    every candidate to 1.0 — the new floor scales with n so selection
    tests at n <= 130 exercise real cost differences."""
    from repro.compiler.costing import tile_floor
    assert tile_floor(64, 2) < tile_floor(128, 2) < tile_floor(130, 2)
    assert tile_floor(64, 1) == pytest.approx(0.5)
    assert tile_floor(64, 3) == pytest.approx(0.5)   # width>1 capped by tile
    assert tile_floor(0, 2) == tile_floor(1, 2)      # degenerate graphs
    assert tile_floor(64, 0) == 1.0


def test_kernel_join_cost_devices_term():
    """More devices: per-device work shrinks, a log2(d) collective term
    appears — never free, monotone in d for fixed work."""
    from repro.compiler.costing import _kernel_join_cost
    axes = ((0, 1), (0, 1))
    c1 = _kernel_join_cost(2, axes, 1024, 1 << 27, devices=1)
    c8 = _kernel_join_cost(2, axes, 1024, 1 << 27, devices=8)
    assert c8 < c1                       # sharding pays off at n=1024
    import math
    tiny = _kernel_join_cost(2, axes, 16, 1 << 27, devices=8)
    assert tiny > math.log2(8)           # collective term never waived


# -- static shard-legality diagnostics ----------------------------------------------


def _plan_and_info(n=24, deg=4.0, seed=13):
    from repro import compiler
    from repro.analysis import GraphInfo
    from repro.core.counting import CountingEngine
    from repro.core.pattern import cycle
    from repro.graph import generators as gen
    g = gen.erdos_renyi(n, deg, seed=seed)
    cp = compiler.compile(cycle(4), g, counter=CountingEngine(g),
                          cache=False)
    return cp.plan, GraphInfo.from_graph(g)


def test_shard_check_diagnostics():
    from repro import analysis
    plan, info = _plan_and_info(n=24)

    assert analysis.shard_check(plan, info, 1).diagnostics == []

    res = analysis.shard_check(plan, info, 48)      # n < shards
    assert any(d.code == "shard-small-graph" for d in res.warnings)

    res = analysis.shard_check(plan, info, 5)       # 24 % 5 != 0
    assert any(d.code == "shard-indivisible" for d in res.warnings)
    assert res.ok                                   # advisory only

    res = analysis.shard_check(plan, info, 4, budget=1)
    assert any(d.code == "shard-budget-overflow" for d in res.warnings)


def test_precertify_num_shards_is_noop():
    """Per-shard blocks are certified by the global certificate (a
    slice max never exceeds the global max), so num_shards must not
    change precertification output."""
    from repro import analysis
    plan, info = _plan_and_info(n=40, deg=5.0)
    assert analysis.precertify(plan, info) == \
        analysis.precertify(plan, info, num_shards=8)
