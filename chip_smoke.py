"""Smoke test of the exact-counting main path on a TPU.

    python chip_smoke.py [--seed S]      # one chip
    python chip_smoke.py --mesh 4        # only the sharded census, 4 chips vs chip 0

It runs what ``python -m repro.launch.mine --app motif --k 4`` and
``--app chain --k 5 --local-counts`` run (``compiler.compile`` →
``CompiledPlan``), with the Pallas join kernels compiled for the chip,
and checks every answer in the same process:

* small: a 24-vertex graph; each 4-motif and the 5-chain against brute
  force, the per-vertex 5-chain vector against Σ_u = 5 · count, and the
  matreduce triangle kernel against the host clique count;
* mid: 400 vertices at the full graph's mean degree, small enough for
  the dense |cut| = 3 oracle: kernel plans integer-equal to the same
  plans lowered with ``cutjoin_kernel=False`` (the f64 XLA join);
* full: SNAP Wiki-Vote's vertex and edge counts (Erdős–Rényi from
  ``--seed``: 7,115 vertices, ~103.6k edges, mean degree ~29.1).
  Compile, first execute and a warm execute (plan-cache hit, fresh
  engine) are timed.  The census is checked against the XLA oracle and
  against degree formulas for the 3-star and the 4-path; the 5-chain
  vertex vector against the XLA oracle, and the 5-chain count (its
  |cut| = 3 join has no dense oracle at n³ cells) against an all-XLA
  plan restricted to |cut| <= 2.

It exits non-zero, printing no result, when JAX finds no TPU, when a
join kernel ran interpreted, when any compile fallback was taken, or
when an answer differs.  The last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FULL_N, FULL_M = 7115, 103689       # SNAP Wiki-Vote: vertices, edges
# gen.erdos_renyi draws 1.2x its nominal n * deg / 2 pairs and keeps them
# all (dedup drops ~0.2%), so Wiki-Vote's mean degree 29.15 takes 24.32
FULL_DEG = 24.32
MID_N, SMALL_N, SMALL_DEG = 400, 24, 5.0
EXPECTED_MODE = "compiled"           # kernel.calls mode every join must show
# (route, cut size) -> the ops wrapper a single-device kernel route calls
KERNEL_OPS = {("kernel", 1): "cutjoin_reduce", ("kernel", 2): "cutjoin_reduce",
              ("kernel", 3): "cutjoin_reduce3",
              ("kernel-keep", 2): "cutjoin_reduce_keep",
              ("kernel-keep", 3): "cutjoin_reduce3_keep"}


def log(msg: str):
    print(msg, flush=True)


def require(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def routes(tracer) -> collections.Counter:
    return collections.Counter(
        (s.attrs["route"], s.attrs.get("cut_size"))
        for s in tracer.walk() if "route" in s.attrs)


def census(g, tracer, **kw):
    """``launch.mine --app motif --k 4``: one joint compile, edge-induced
    counts read off the plan, the vertex-induced table by the overlay
    transform.  Returns (plan, edge-induced counts, compile s, execute s)."""
    from repro import compiler
    from repro.core.counting import solve_overlay
    from repro.core.motifs import motif_patterns
    pats = motif_patterns(4)
    t = time.perf_counter()
    cp = compiler.compile(pats, g, **kw)
    cp.tracer = tracer
    t_compile = time.perf_counter() - t
    t = time.perf_counter()
    e = {p: cp.count(p) for p in pats}
    solve_overlay(4, e)
    return cp, e, t_compile, time.perf_counter() - t


def chain_local(g, tracer, **kw):
    """``launch.mine --app chain --k 5 --local-counts``: the 5-chain
    count and its per-vertex vector off one local plan.  Returns (plan,
    count, vertex vector, compile s, execute s)."""
    from repro import compiler
    from repro.api import plan_vertex_counts, top_vertices
    from repro.core.pattern import chain
    p = chain(5)
    t = time.perf_counter()
    cp = compiler.compile(p, g, local=True, **kw)
    cp.tracer = tracer
    t_compile = time.perf_counter() - t
    t = time.perf_counter()
    c = cp.count(p)
    vc = plan_vertex_counts(cp, p)
    top_vertices(vc, 10)
    return cp, c, vc, t_compile, time.perf_counter() - t


def phase_small(seed: int, kern):
    import numpy as np
    from repro.core.cliques import clique_count
    from repro.core.counting import brute_force_edge_induced
    from repro.core.pattern import chain
    from repro.graph import generators as gen
    from repro.kernels import ops
    g = gen.erdos_renyi(SMALL_N, SMALL_DEG, seed=seed)
    _, e, _, _ = census(g, kern)
    for p, v in e.items():
        require(v == brute_force_edge_induced(g, p),
                f"small: {sorted(p.edges)} {v} != brute force")
    _, c, vc, _, _ = chain_local(g, kern)
    require(c == brute_force_edge_induced(g, chain(5)),
            "small: 5-chain != brute force")
    require(vc.sum() == 5 * c, "small: sum of vertex counts != 5 * count")
    tri = ops.triangle_count(g.dense_adjacency(np.float32, pad=False))
    require(float(tri) == clique_count(g, 3),
            "small: matreduce triangle kernel != clique count")
    log(f"small  n={g.n} m={g.m}: 6 motifs, 5-chain {c:.0f} and the "
        f"triangle kernel equal brute force")


def phase_mid(seed: int, kern, xla):
    import numpy as np
    from repro.graph import generators as gen
    g = gen.erdos_renyi(MID_N, FULL_DEG, seed=seed)
    cp, e, _, _ = census(g, kern)
    _, e_x, _, _ = census(g, xla, cutjoin_kernel=False, counter=cp.counter)
    require(e == e_x, "mid: census kernel plan != XLA oracle")
    cp, c, vc, _, _ = chain_local(g, kern)
    _, c_x, vc_x, _, _ = chain_local(g, xla, cutjoin_kernel=False,
                                     counter=cp.counter)
    require(c == c_x and np.array_equal(vc, vc_x),
            "mid: 5-chain kernel plan != XLA oracle")
    log(f"mid    n={g.n} m={g.m}: census and 5-chain integer-equal to "
        f"the XLA oracle (5-chain {c:.0f})")


def phase_full(seed: int, kern, xla, times: dict):
    import numpy as np
    from repro import compiler
    from repro.core.cliques import clique_count
    from repro.core.pattern import chain, star
    from repro.graph import generators as gen
    g = gen.erdos_renyi(FULL_N, FULL_DEG, seed=seed)
    log(f"full   n={g.n} m={g.m}")
    require(abs(g.m - FULL_M) < 0.005 * FULL_M, "full: edge count off")

    cp, e, times["census compile"], times["census first execute"] = \
        census(g, kern)
    cp2, e2, times["census compile (plan-cache hit)"], \
        times["census warm execute"] = census(g, kern)
    require(cp2.from_cache and e2 == e, "full: warm census differs")
    _, e_x, _, _ = census(g, xla, cutjoin_kernel=False, counter=cp.counter)
    require(e == e_x, "full: census kernel plan != XLA oracle")
    d = g.degrees.astype(object)             # exact Python integers
    tri = clique_count(g, 3)
    stars = sum(x * (x - 1) * (x - 2) // 6 for x in d)
    paths = sum((d[u] - 1) * (d[v] - 1) for u, v in g.edges) - 3 * tri
    require(e[star(4).canonical()] == stars, "full: 3-star != degree formula")
    require(e[chain(4).canonical()] == paths, "full: 4-path != degree formula")
    log(f"  census: 6 motifs equal the XLA oracle; 3-star {stars}, 4-path "
        f"{paths} equal the degree formulas")
    del cp, cp2, e2
    gc.collect()

    cp, c, vc, times["chain compile"], times["chain first execute"] = \
        chain_local(g, kern)
    cp2, c2, vc2, times["chain compile (plan-cache hit)"], \
        times["chain warm execute"] = chain_local(g, kern)
    require(cp2.from_cache and c2 == c and np.array_equal(vc2, vc),
            "full: warm 5-chain differs")
    del cp2, vc2
    gc.collect()
    from repro.api import plan_vertex_counts
    cp_x = compiler.compile(chain(5), g, local=True, cutjoin_kernel=False,
                            counter=cp.counter)
    cp_x.tracer = xla
    require(np.array_equal(plan_vertex_counts(cp_x, chain(5)), vc),
            "full: 5-chain vertex vector != XLA oracle")
    cp_2 = compiler.compile(chain(5), g, cache=False, max_cutjoin_cut=2,
                            cutjoin_kernel=False, counter=cp.counter)
    cp_2.tracer = xla
    require(cp_2.count(chain(5)) == c,
            "full: 5-chain != all-XLA |cut| <= 2 plan")
    require(vc.sum() == 5 * c, "full: sum of vertex counts != 5 * count")
    log(f"  5-chain {c:.0f}: vertex vector equals the XLA oracle, count "
        f"equals the |cut| <= 2 XLA plan and sum(vertex counts) / 5")


def check_kernels(kern):
    """Every join kernel the plans selected ran compiled, none fell back."""
    from repro import obs
    seen = routes(kern)
    log("kernel-plan routes (route, |cut|): "
        + json.dumps({f"{r}/{c}": v for (r, c), v in sorted(
            seen.items(), key=lambda kv: str(kv[0]))}))
    calls = obs.snapshot().get("kernel.calls", {})
    log(f"kernel.calls: {json.dumps(calls, sort_keys=True)}")
    for label in calls:
        require(f"mode={EXPECTED_MODE}" in label,
                f"kernel call not {EXPECTED_MODE}: {label}")
    for (route, cut) in seen:
        if route.startswith("xla"):
            raise RuntimeError(f"kernel plan took {route} at |cut| {cut}")
        op = KERNEL_OPS.get((route, cut))
        if op is not None:
            require(obs.get("kernel.calls", op=op, cut=cut,
                            mode=EXPECTED_MODE) > 0,
                    f"{op} (|cut| {cut}) selected but never called")


def check_fallbacks():
    from repro import obs
    fb = {name: series for name, series in obs.snapshot().items()
          if "fallback" in name}
    log(f"fallback counters (cutjoin.kernel_fallbacks, "
        f"engine.compiler_fallbacks, api.compile_fallbacks, ...): "
        f"{json.dumps(fb, sort_keys=True) if fb else 'none'}")
    require(not any(v for series in fb.values() for v in series.values()),
            "a fallback was taken")


def run_single(seed: int):
    from repro import obs
    kern, xla = obs.Tracer(), obs.Tracer()
    times: dict = {}
    t = time.perf_counter()
    phase_small(seed, kern)
    times["small phase"] = time.perf_counter() - t
    t = time.perf_counter()
    phase_mid(seed, kern, xla)
    times["mid phase"] = time.perf_counter() - t
    phase_full(seed, kern, xla, times)
    for name, s in times.items():
        log(f"  time {name}: {s:.3f} s")
    log("oracle-plan routes: " + json.dumps(
        {f"{r}/{c}": v for (r, c), v in routes(xla).items()}))
    check_kernels(kern)
    check_fallbacks()


def run_mesh(seed: int, devices: int):
    """The sharded census over ``devices`` chips against chip 0 alone."""
    from repro import obs
    from repro.distributed import meshes
    from repro.graph import generators as gen
    g = gen.erdos_renyi(FULL_N, FULL_DEG, seed=seed)
    log(f"mesh   n={g.n} m={g.m}, {devices} devices")
    one, many = obs.Tracer(), obs.Tracer()
    _, e1, tc1, te1 = census(g, one, cache=False)
    mesh = meshes.data_mesh(devices)
    _, ed, tcd, ted = census(g, many, cache=False, mesh=mesh)
    log(f"  time 1 device: compile {tc1:.3f} s, execute {te1:.3f} s")
    log(f"  time {devices} devices: compile {tcd:.3f} s, execute "
        f"{ted:.3f} s")
    seen = {r for r, _ in routes(many)}
    log(f"  routes 1 device: {dict(routes(one))}")
    log(f"  routes {devices} devices: {dict(routes(many))}")
    require(ed == e1, f"{devices}-device census != 1 device")
    require({"einsum-sharded", "kernel-sharded"} <= seen,
            f"sharded contract and join routes not both taken: {seen}")
    log(f"  census integer-equal across 1 and {devices} devices")
    check_fallbacks()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="run only the census sharded over N chips, "
                    "against chip 0 alone")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if args.mesh is not None and len(devs) < args.mesh:
        print(f"chip_smoke: --mesh {args.mesh} needs {args.mesh} chips, "
              f"found {len(devs)}", file=sys.stderr)
        return 2
    from repro.kernels import ops
    from repro.launch.jax_cache import setup_compile_cache
    log(f"device: {devs[0].device_kind} x{len(devs)}; compile cache: "
        f"{setup_compile_cache()}")
    require(not ops._auto_interpret(None), "kernels would run interpreted")
    if args.mesh is not None:
        run_mesh(args.seed, args.mesh)
    else:
        run_single(args.seed)
    for d in devs[:args.mesh or 1]:
        log(f"peak_bytes_in_use {d}: "
            f"{(d.memory_stats() or {}).get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
