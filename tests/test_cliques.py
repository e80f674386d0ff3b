"""Clique path: the device pass and ordered enumeration vs tensor engine
vs brute force."""
import itertools
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import obs
from repro.core import cliques
from repro.core.cliques import (clique_count, clique_minus_edge_count,
                                pseudo_clique_count)
from repro.core.counting import CountingEngine, brute_force_vertex_induced
from repro.core.pattern import Pattern, clique
from repro.graph.generators import erdos_renyi, triangle_rich
from repro.graph.storage import Graph

GRAPHS = [erdos_renyi(25, 6.0, seed=1), triangle_rich(30, 4, seed=2)]
# more edges than one step of the device pass, and not a multiple of it:
# the last step is padded
PADDED = erdos_renyi(64, 40.0, seed=7)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def brute_cliques(g: Graph, k: int) -> int:
    """k-cliques by testing every k-subset of the vertices."""
    a = g.dense_adjacency(np.int8, pad=False).astype(bool)
    subsets = np.array(list(itertools.combinations(range(g.n), k)))
    ok = np.ones(len(subsets), bool)
    for i, j in itertools.combinations(range(k), 2):
        ok &= a[subsets[:, i], subsets[:, j]]
    return int(ok.sum())


@pytest.mark.parametrize("gi", range(len(GRAPHS)))
@pytest.mark.parametrize("k", [3, 4, 5])
def test_clique_count_matches_bruteforce(gi, k):
    g = GRAPHS[gi]
    want = 0
    for vs in itertools.combinations(range(g.n), k):
        if all(g.has_edge(a, b) for a, b in itertools.combinations(vs, 2)):
            want += 1
    assert clique_count(g, k) == want


@pytest.mark.parametrize("k", [3, 4])
def test_clique_minus_edge_matches_bruteforce(k):
    g = GRAPHS[0]
    p = Pattern(k, set(clique(k).edges) - {(0, 1)})
    want = brute_force_vertex_induced(g, p)
    assert clique_minus_edge_count(g, k) == want


def test_engine_routes_cliques_consistently():
    """hom(K_k) via the clique path equals the paper's identity and the
    tensor path on a small graph."""
    import math
    g = GRAPHS[0]
    eng = CountingEngine(g)
    for k in (3, 4):
        assert eng.hom(clique(k)) == math.factorial(k) * clique_count(g, k)
    # triangle double-check against the tensor engine directly
    import jax.numpy as jnp
    from repro.core import homomorphism as H
    A = jnp.asarray(g.dense_adjacency(np.float64, pad=False))
    assert float(H.hom_count(clique(3), A)) == eng.hom(clique(3))


def test_plan_too_wide_raises():
    from repro.core import homomorphism as H
    from repro.core.homomorphism import PlanTooWide
    import jax.numpy as jnp
    g = erdos_renyi(64, 6.0, seed=3)
    A = jnp.asarray(g.dense_adjacency(np.float32, pad=False))
    with pytest.raises(PlanTooWide):
        H.hom_count(clique(5), A, budget=1 << 8)


def test_pseudo_clique_count_large_graph():
    g = erdos_renyi(300, 10.0, seed=4)
    total = pseudo_clique_count(g, 4)
    eng = CountingEngine(g)
    from repro.core.pattern import pseudo_clique
    want = eng.vertex_induced(clique(4))
    for p in pseudo_clique(4, 1):
        want += eng.vertex_induced(p)
    assert total == want


@pytest.mark.parametrize("gi", range(len(GRAPHS) + 1))
def test_device_pass_matches_enumeration_and_bruteforce(gi):
    g = (GRAPHS + [PADDED])[gi]
    if g is PADDED:
        assert g.m > cliques.CHUNK and g.m % cliques.CHUNK
    want = (6 * brute_cliques(g, 3), 24 * brute_cliques(g, 4))
    assert want == (6 * clique_count(g, 3), 24 * clique_count(g, 4))
    assert cliques.clique_homs(g) == want


def test_device_pass_fills_both_homs_once():
    """One pass a graph: hom(K3) and hom(K4) both land in the memo, and
    the second read is a memo hit, not a second pass."""
    g = GRAPHS[1]
    eng = CountingEngine(g)
    before = obs.get("cliques.passes", route="device")
    assert eng.hom(clique(4)) == 24 * clique_count(g, 4)
    assert eng.hom(clique(3)) == 6 * clique_count(g, 3)
    assert obs.get("cliques.passes", route="device") == before + 1
    assert eng.stats["hom_hits"] == 1


def test_relabelled_graph_adds_no_trace():
    """The pass's shapes depend on (n, m) only: a job on a relabelled
    copy of the graph runs the program already traced."""
    g = PADDED
    perm = np.random.default_rng(5).permutation(g.n)
    h = Graph(g.n, perm[g.edges])
    assert h.m == g.m and not np.array_equal(h.edges, g.edges)
    assert CountingEngine(g).hom(clique(4)) == 24 * clique_count(g, 4)
    before = obs.get("jax.traces", span="enumerate")
    assert CountingEngine(h).hom(clique(4)) == 24 * clique_count(g, 4)
    assert obs.get("jax.traces", span="enumerate") == before


def test_route_is_enumeration_above_the_int32_bound():
    """Past n = 46,340 a row of the pass could leave int32: K3 and K4
    are enumerated on the host, and the pass refuses the graph."""
    n = cliques.PASS_MAX_N + 1
    g = Graph(n, [(0, 1), (1, 2), (0, 2), (2, 3), (n - 1, n - 2)])
    eng = CountingEngine(g)
    assert eng.clique_route(3) == eng.clique_route(4) == "enumeration"
    assert CountingEngine(GRAPHS[0]).clique_route(5) == "enumeration"
    before = obs.get("cliques.passes", route="host")
    assert eng.hom(clique(3)) == 6
    assert obs.get("cliques.passes", route="host") == before + 1
    with pytest.raises(ValueError):
        cliques.clique_homs(g)


def test_device_pass_on_a_mesh_keeps_the_adjacency_sharded():
    """On a mesh-bound engine the pass builds its own int8 adjacency from
    the edges: the dense f64 adjacency is never built."""
    code = """
        from repro.core.cliques import clique_count
        from repro.core.counting import CountingEngine
        from repro.core.pattern import clique, cycle
        from repro.distributed import meshes
        from repro.graph import generators as gen

        g = gen.erdos_renyi(97, 12.0, seed=4)
        eng = CountingEngine(g, mesh=meshes.data_mesh())
        assert eng.mesh is not None and eng.contract_shards() == 4
        assert eng.hom(clique(4)) == 24 * clique_count(g, 4) > 0
        assert eng.hom(clique(3)) == 6 * clique_count(g, 3)
        assert eng.hom(cycle(4)) == CountingEngine(g).hom(cycle(4))
        assert eng._A_dense is None
        print("ok")
    """
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr[-3000:]
