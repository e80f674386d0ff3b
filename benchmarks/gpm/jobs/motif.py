"""How a k-motif census job calls the program.

What ``python -m repro.launch.mine --app motif --k K`` runs: one joint
``compiler.compile`` of the connected K-vertex patterns, the edge-induced
count of each read off the plan, then the vertex-induced table by the
overlay transform.  Patterns are named by their shape so that the
reference, which imports nothing of the program, can be compared.
"""
from __future__ import annotations

import time

import jax

# (edges, sorted degree sequence) -> name; unique among connected
# 4-vertex graphs
SHAPES = {(3, (1, 1, 1, 3)): "star3", (3, (1, 1, 2, 2)): "path4",
          (4, (1, 2, 2, 3)): "tailed_triangle", (4, (2, 2, 2, 2)): "cycle4",
          (5, (2, 2, 3, 3)): "diamond", (6, (3, 3, 3, 3)): "clique4"}


def shape_name(p) -> str:
    degrees = [0] * p.n
    for u, v in p.edges:
        degrees[u] += 1
        degrees[v] += 1
    return SHAPES[(len(p.edges), tuple(sorted(degrees)))]


def run(n: int, edges, params: dict, tracer=None):
    """-> (answer, info): the two count tables by pattern name, and the
    host seconds spent in ``compiler.compile``."""
    from repro import compiler
    from repro.core.counting import solve_overlay
    from repro.core.motifs import motif_patterns
    from repro.graph.storage import Graph
    k = int(params["k"])
    with jax.profiler.TraceAnnotation("gpm.graph_build"):
        g = Graph(n, edges)
    patterns = motif_patterns(k)
    with jax.profiler.TraceAnnotation("gpm.plan_search"):
        t = time.perf_counter()
        cp = compiler.compile(patterns, g)
        plan_search_s = time.perf_counter() - t
    cp.tracer = tracer
    with jax.profiler.TraceAnnotation("gpm.execute"):
        edge = {p: cp.count(p) for p in patterns}
        vind = solve_overlay(k, edge)
    answer = {"edge_induced": {shape_name(p): float(v)
                               for p, v in edge.items()},
              "vertex_induced": {shape_name(p): float(v)
                                 for p, v in vind.items()}}
    return answer, {"plan_search_s": plan_search_s}
