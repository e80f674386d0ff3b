"""How a k-chain local-count job calls the program.

What ``python -m repro.launch.mine --app chain --k K --local-counts``
runs: one ``compiler.compile(chain(K), local=True)``, the chain count and
the per-vertex vector off that plan, then the ``top`` hottest vertices.
"""
from __future__ import annotations

import time

import jax


def run(n: int, edges, params: dict, tracer=None):
    """-> (answer, info): count, per-vertex vector and top (value,
    vertex) pairs, and the host seconds spent in ``compiler.compile``."""
    from repro import compiler
    from repro.api import plan_vertex_counts, top_vertices
    from repro.core.pattern import chain
    from repro.graph.storage import Graph
    p = chain(int(params["k"]))
    with jax.profiler.TraceAnnotation("gpm.graph_build"):
        g = Graph(n, edges)
    with jax.profiler.TraceAnnotation("gpm.plan_search"):
        t = time.perf_counter()
        cp = compiler.compile(p, g, local=True)
        plan_search_s = time.perf_counter() - t
    cp.tracer = tracer
    with jax.profiler.TraceAnnotation("gpm.execute"):
        count = cp.count(p)
        vertex = plan_vertex_counts(cp, p)
        top = top_vertices(vertex, int(params["top"]))
    return ({"count": float(count), "vertex": vertex, "top": top},
            {"plan_search_s": plan_search_s})
