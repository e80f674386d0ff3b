"""How a k-motif census job calls the program on a mesh of chips.

What ``python -m repro.launch.mine --app motif --k K --mesh D`` runs:
``jobs/motif.py``'s census, with ``compiler.compile`` given the 1-D
``("data",)`` mesh of the first ``params["mesh"]`` devices, so that the
Contract runs with the adjacency row-sharded over them and the joins
block-sharded.  Patterns are named by ``jobs/motif.py``'s shapes.

A job that runs out of device memory ends the run (exit status 1, no
result line): the deployment does not fit the mesh, which no count can
show.
"""
from __future__ import annotations

import time

import jax

motif = bench.module("jobs", "motif")  # noqa: F821  (set by Bench.module)


def run(n: int, edges, params: dict, tracer=None):
    """-> (answer, info): the two count tables by pattern name, and the
    host seconds spent in ``compiler.compile``."""
    try:
        return _census(n, edges, params, tracer)
    except Exception as e:
        if "RESOURCE_EXHAUSTED" in str(e):
            raise SystemExit(f"motif_mesh: the census does not fit the "
                             f"devices' memory: {e}")
        raise


def _census(n: int, edges, params: dict, tracer):
    from repro import compiler
    from repro.core.counting import solve_overlay
    from repro.core.motifs import motif_patterns
    from repro.distributed.meshes import data_mesh
    from repro.graph.storage import Graph
    k = int(params["k"])
    mesh = data_mesh(int(params["mesh"]))
    with jax.profiler.TraceAnnotation("gpm.graph_build"):
        g = Graph(n, edges)
    patterns = motif_patterns(k)
    with jax.profiler.TraceAnnotation("gpm.plan_search"):
        t = time.perf_counter()
        cp = compiler.compile(patterns, g, mesh=mesh)
        plan_search_s = time.perf_counter() - t
    cp.tracer = tracer
    with jax.profiler.TraceAnnotation("gpm.execute"):
        edge = {p: cp.count(p) for p in patterns}
        vind = solve_overlay(k, edge)
    answer = {"edge_induced": {motif.shape_name(p): float(v)
                               for p, v in edge.items()},
              "vertex_induced": {motif.shape_name(p): float(v)
                                 for p, v in vind.items()}}
    return answer, {"plan_search_s": plan_search_s}
